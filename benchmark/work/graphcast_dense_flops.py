"""Matmul FLOPs one GraphCast train step needs."""


def work(info: dict, calls: float = 0) -> float:
    """Twice rows x in x out of every Dense layer of the forward pass, times
    three (forward, and the two products of the backward pass). What
    ``remat`` computes a second time is not useful work and is not counted,
    so a rematerialised step cannot pass three quarters of the roofline by
    this count."""
    L, C = info["latent"], info["channels"]
    ng, nm = info["n_grid"], info["n_mesh"]
    em, eg, ed = info["e_mesh"], info["e_g2m"], info["e_m2g"]
    embed = (ng * ((C + 4) * L + L * L) + nm * (4 * L + L * L)
             + (em + eg + ed) * (4 * L + L * L))
    edge = lambda n_src, n_dst, e: (n_src + n_dst + 2 * e) * L * L
    node = lambda n: n * 3 * L * L
    enc = edge(ng, nm, eg) + node(nm) + ng * 2 * L * L
    proc = info["processor_layers"] * (edge(nm, nm, em) + node(nm))
    dec = edge(nm, ng, ed) + node(ng)
    head = ng * (L * L + L * C)
    return 3 * 2.0 * (embed + enc + proc + dec + head)
