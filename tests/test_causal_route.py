"""ISSUE 52: which kernels a plain causal attention call takes.

``parallel/sequence.py::_flash_dense`` sends ``causal=True`` without a
``kv_mask`` through ``_splash_dense`` under ``CausalMask`` at EVERY head size
(a head of 128 too) wherever the splash kernels' self-check latched that
grouping and head and the one backward kernel takes the shape
(``_causal_splash``); every other call keeps the library's flash kernels,
behind their own check. ``train/lm.py::resolve_attention`` asks for the check of
the kernels the route runs, no other, and names the route. The kernels run in
the Mosaic interpreter here (``conftest.py::tpu_interpret``); the chip's
numbers are in PERF.md section 6, PR 52."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dgraph_tpu import config as cfg
from dgraph_tpu.obs.metrics import default_registry
from dgraph_tpu.ops import pallas_attention
from dgraph_tpu.parallel import sequence as seq
from dgraph_tpu.train import lm

ROWS, HEAD = 512, 128


@pytest.fixture
def latches(monkeypatch):
    """No check latched, the flag on auto; a test latches what it means to."""
    monkeypatch.setattr(seq, "_splash_verified", set())
    monkeypatch.setattr(seq, "_flash_verified", False)
    monkeypatch.setattr(cfg, "use_flash_attention", None)
    return seq._splash_verified


@pytest.fixture
def routes(monkeypatch, latches):
    """The two kernel routes of ``_flash_dense`` replaced by recorders (a
    routing decision needs no kernel): ``taken`` lists which ran."""
    taken = []

    def recorder(name):
        def route(qh, kh, vh, **kw):
            taken.append((name, kh.shape[1], kw.get("mask")))
            return jnp.zeros(qh.shape[:2] + vh.shape[-1:], qh.dtype)
        return route

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(seq, "_splash_dense", recorder("splash"))
    monkeypatch.setattr(seq, "_flash_kernels", recorder("flash"))
    return taken


def qkv(group, kv_heads, dtype, rows=ROWS, head=HEAD, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((rows, h, head)), dtype)
            for h in (kv_heads * group, kv_heads, kv_heads,
                      kv_heads * group)]


@pytest.mark.parametrize("group,kv_heads", [(1, 2), (7, 2), (16, 1)])
def test_a_plain_causal_call_at_a_head_of_128_runs_the_splash_kernels(
        tpu_interpret, monkeypatch, latches, group, kv_heads):
    """Ouro's, SmallThinker's and Nemotron's groupings: once ``("causal",
    group, 128)`` is latched the call is ONE ``_splash_dense`` under
    ``CausalMask``, its backward the one kernel, and the forward and all three
    gradients are the dense oracle's (tiles of 128 rows: whole, cut and
    skipped ones occur)."""
    monkeypatch.setattr(seq, "FLASH_BLOCK", 128)
    latches.add(("causal", group, HEAD))
    q, k, v, w = qkv(group, kv_heads, jnp.float32)
    calls, real = [], seq._splash_dense
    monkeypatch.setattr(seq, "_splash_dense", lambda *a, **kw: (
        calls.append(kw["mask"]), real(*a, **kw))[1])

    def both(attend):
        loss = jax.checkpoint(lambda *a: (attend(*a) * w).sum())
        out = attend(q, k, v)
        return [np.asarray(t) for t in (out, *jax.jit(
            jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))]

    counted = lambda: np.array([
        default_registry.snapshot()["counters"].get(n, 0)
        for n in ("attn.bwd_calls", "attn.bwd_one_kernel")])
    before = counted()
    got = both(lambda *a: seq._flash_dense(*a, causal=True, scale=None,
                                           kv_mask=None))
    assert calls == [seq.CausalMask(ROWS)] * 2  # the forward's, the grad's
    assert (counted() - before == [1, 1]).all()
    want = both(lambda *a: seq.dense_attention(*a, causal=True))
    for mine, oracle in zip(got, want):
        assert mine.shape == oracle.shape
        np.testing.assert_allclose(mine, oracle, rtol=2e-5, atol=2e-5)


BIG = 65536  # rows whose KV head's blocks pass VMEM_BUDGET at a head of 128


@pytest.mark.parametrize("case,kw", [
    ("latched", {}),
    ("kv_mask", {"kv_mask": True}),
    ("not causal", {"causal": False}),
    ("another grouping latched", {"latch": ("causal", 2, HEAD)}),
    ("another head latched", {"latch": ("causal", 4, 64)}),
    ("rows past the budget", {"rows": BIG}),
    ("a head past the budget", {"rows": 16384, "head": 1024,
                                "latch": ("causal", 4, 1024)}),
])
def test_every_other_call_keeps_the_flash_kernels(routes, latches, case, kw):
    """The route follows from the call's own arguments and the latch: with a
    ``kv_mask``, not causal, a grouping or head whose check has not passed, or
    a ``T`` / ``D`` that ``pallas_attention.applies`` refuses, the call takes
    the library's flash kernels as it did."""
    rows, head = kw.get("rows", ROWS), kw.get("head", HEAD)
    latches.add(kw.get("latch", ("causal", 4, HEAD)))
    q, k, v, _ = (jax.ShapeDtypeStruct((rows, h, head), jnp.bfloat16)
                  for h in (8, 2, 2, 8))
    mask = jax.ShapeDtypeStruct((rows,), jnp.float32) \
        if kw.get("kv_mask") else None
    assert pallas_attention.applies(
        rows, head, head, 2, seq.flash_tile(rows)) \
        == (case not in ("rows past the budget", "a head past the budget"))
    out = jax.eval_shape(
        lambda q, k, v, m: seq._flash_dense(
            q, k, v, causal=kw.get("causal", True), scale=None, kv_mask=m),
        q, k, v, mask)
    assert out.shape == q.shape
    if case == "latched":
        assert routes == [("splash", 2, seq.CausalMask(rows))]
    else:
        assert routes == [("flash", 2, None)]


@pytest.mark.parametrize("group", [7, 16])
def test_grouped_keys_and_values_are_not_repeated_on_the_new_route(
        monkeypatch, latches, group):
    """``repeat_kv`` is traced by the flash route alone (the library's kernels
    know one head count); the splash kernels read the ``Hkv`` heads where
    they lie."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    key_heads = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(seq, "_splash_dense", lambda q, k, v, **kw: (
        key_heads.append(("splash", k.shape[1])),
        jnp.zeros(q.shape, q.dtype))[1])
    monkeypatch.setattr(fa, "flash_attention", lambda q, k, v, **kw: (
        key_heads.append(("flash", k.shape[1])),
        jnp.zeros(q.shape, q.dtype))[1])
    repeats, real = [], seq.repeat_kv
    monkeypatch.setattr(seq, "repeat_kv", lambda *a: (
        repeats.append(1), real(*a))[1])
    q, k, v, _ = (jax.ShapeDtypeStruct((ROWS, h, HEAD), jnp.bfloat16)
                  for h in (2 * group, 2, 2, 2 * group))
    # (a function a trace: eval_shape keeps what it traced)
    attend = lambda: lambda q, k, v: seq._flash_dense(
        q, k, v, causal=True, scale=None, kv_mask=None)
    jax.eval_shape(attend(), q, k, v)  # nothing latched: the library's kernels
    assert key_heads == [("flash", 2 * group)] and repeats == [1]
    latches.add(("causal", group, HEAD))
    jax.eval_shape(attend(), q, k, v)
    assert key_heads[1:] == [("splash", 2)] and repeats == [1]


@pytest.mark.parametrize("check,args,route", [
    # (what is latched, _flash_applicable's arguments, engages?)
    ("splash", {"causal": True}, True),
    ("splash", {"causal": True, "kv_mask": True}, False),
    ("splash", {"causal": False}, False),
    ("splash", {}, False),
    ("flash", {"causal": True}, True),
    ("flash", {"causal": True, "kv_mask": True}, True),
    ("flash", {}, True),
    ("neither", {"causal": True}, False),
])
def test_neither_check_stands_in_for_the_other(monkeypatch, latches, check,
                                               args, route):
    """The splash check engages the plain causal route and nothing else; the
    flash branch (a ``kv_mask``, not causal, the default arguments) is guarded
    by ``_flash_verified``, pinned flag or not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(cfg, "use_flash_attention", True)
    if check == "splash":
        latches.add(("causal", 4, HEAD))
    monkeypatch.setattr(seq, "_flash_verified", check == "flash")
    q = jnp.zeros((ROWS, 8, HEAD), jnp.bfloat16)
    kv_mask = jnp.ones((ROWS,)) if args.get("kv_mask") else None
    assert seq._flash_applicable(
        q, require_pinned=True, group=4, causal=args.get("causal", False),
        kv_mask=kv_mask) is route


@pytest.fixture
def checks(monkeypatch, latches):
    """A described chip: the two self-checks replaced by recorders that latch
    as the real ones do."""
    asked = []

    def splash(mask, group, *, head_dim=128, v_head_dim=None, **_):
        asked.append(("splash", mask.name, group, head_dim))
        latches.add((mask.name, group, seq._head_key(head_dim, v_head_dim)))
        return True

    def flash():
        asked.append(("flash",))
        monkeypatch.setattr(seq, "_flash_verified", True)
        return True

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(seq, "_splash_selfcheck", splash)
    monkeypatch.setattr(seq, "_flash_selfcheck", flash)
    return asked


@pytest.mark.parametrize("cell,world,rows,heads,group,mask,asked,name", [
    ("ouro_2p6b", 1, 8192, 16, 1, None,
     [("splash", "causal", 1, 128)], "splash"),
    ("nemotron3_nano_30b_a3b", 1, 8192, 32, 16, None,
     [("splash", "causal", 16, 128)], "splash"),
    ("smallthinker_21b_a3b, full", 1, 16384, 28, 7, None,
     [("splash", "causal", 7, 128)], "splash"),
    ("smallthinker_21b_a3b, window", 1, 16384, 28, 7,
     seq.WindowMask(16384, 4096), [("splash", "window", 7, 128)], "splash"),
    ("rows past the budget", 1, BIG, 16, 1, None, [("flash",)], "flash"),
    # Ulysses repeats K and V before the call: one key head a query head
    ("ulysses", 4, 2048, 32, 4, None,
     [("splash", "causal", 1, 128)], "ulysses+splash"),
])
def test_resolve_attention_checks_the_kernels_its_route_runs_and_no_others(
        checks, cell, world, rows, heads, group, mask, asked, name):
    """A plain causal stack asks for the causal splash check at its grouping
    and head, NOT for the flash one, and the route is named for what it is;
    a shape the one backward kernel refuses keeps the flash kernels and their
    check."""
    comm = lm.lm_comm(world)
    got = lm.resolve_attention(comm, "ulysses" if world > 1 else "ring",
                               rows // world, heads, HEAD, mask, group,
                               dtype=jnp.bfloat16)
    assert (checks, got) == (asked, name)
    assert cfg.use_flash_attention is True  # pinned by the check that passed


def test_the_selfcheck_as_called_with_nothing_is_the_flash_kernels(checks):
    """The callers that still reach the library's kernels (a ``kv_mask``, not
    causal) ask with the default arguments; ``rows`` is what turns a plain
    causal caller's check to the splash kernels."""
    assert seq.flash_attention_selfcheck() and checks == [("flash",)]
    del checks[:]
    assert seq.flash_attention_selfcheck(group=16, rows=8192)
    assert seq.flash_attention_selfcheck(group=16, rows=BIG)
    assert seq.flash_attention_selfcheck(group=4, head_dim=64)
    assert checks == [("splash", "causal", 16, 128), ("flash",),
                      ("splash", "causal", 4, 64)]


@pytest.mark.parametrize("causal,padded", [(False, True), (True, True),
                                           (True, False)])
def test_the_kept_route_is_the_oracle_in_interpret_mode(
        tpu_interpret, monkeypatch, causal, padded):
    """What the library's flash kernels still run (``_flash_kernels``: a
    ``kv_mask`` as a second segment id, a non-causal call, a causal shape past
    the one backward kernel's budget), grouped K and V repeated: forward and
    the three gradients against the dense oracle, padded rows included."""
    monkeypatch.setattr(seq, "FLASH_BLOCK", 128)
    q, k, v, w = qkv(2, 2, jnp.float32, rows=256)
    kv_mask = (jnp.arange(256) < 224).astype(jnp.float32) if padded else None

    def both(attend):
        f = lambda *a: (attend(*a, causal=causal, kv_mask=kv_mask) * w).sum()
        return [np.asarray(t) for t in (
            attend(q, k, v, causal=causal, kv_mask=kv_mask),
            *jax.grad(f, argnums=(0, 1, 2))(q, k, v))]

    got = both(lambda *a, **kw: seq._flash_kernels(*a, scale=None, **kw))
    for mine, oracle in zip(got, both(seq.dense_attention)):
        assert mine.shape == oracle.shape
        np.testing.assert_allclose(mine, oracle, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("group,oracle_heads", [(16, 8), (7, 3)])
def test_the_selfchecks_oracle_takes_a_kv_heads_query_heads_in_pieces(
        monkeypatch, group, oracle_heads):
    """The splash self-check's dense oracle holds ``[rows, heads, rows]``
    float32 logits, and the allocator's peaks are the process's
    (``hbm_peak_gb``): past ``ORACLE_HEADS`` query heads a KV head it runs
    them in pieces and sums the head's ``dk`` and ``dv`` (Nemotron's 16 as
    two pieces of 8; 7 as 3 + 3 + 1 here). The check still passes, and still
    fails a kernel whose ``dk`` is wrong."""
    monkeypatch.setattr(seq, "_splash_verified", set())
    monkeypatch.setattr(seq, "ORACLE_HEADS", oracle_heads)
    pieces, real = [], seq.dense_attention
    monkeypatch.setattr(seq, "dense_attention", lambda q, *a, **kw: (
        pieces.append(q.shape[1]), real(q, *a, **kw))[1])
    assert seq._splash_selfcheck(seq.CausalMask(0), group, interpret=True)
    assert ("causal", group, 128) in seq._splash_verified
    whole, rest = divmod(group, oracle_heads)
    # (the forward and the gradient's trace, each KV head of two)
    assert pieces == ([oracle_heads] * 2 * whole + [rest] * 2 * bool(rest)) * 2
    seq._splash_verified.clear()
    splash = seq._splash_dense

    def wrong_dk(q, k, v, **kw):  # the forward's k, 1.25 x its gradient
        return splash(q, k + 0.25 * (k - jax.lax.stop_gradient(k)), v, **kw)

    monkeypatch.setattr(seq, "_splash_dense", wrong_dk)
    assert not seq._splash_selfcheck(seq.CausalMask(0), group, interpret=True)
    assert not seq._splash_verified
