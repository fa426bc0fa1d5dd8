"""Where a GCN cell's row-gather tables live, without a chip: the cell's
train and eval steps compiled at the real size for a described v5e by
``benchmark/tools/rehearse_w4.py`` (its graph, plan and Pallas branches;
~10 s a cell on one chip's program, ~45 s for four), and each compiled
module read by ``dgraph_tpu.analysis.hlo.gather_table_placement``:

    JAX_PLATFORMS=cpu python3 scripts/gather_placement.py --workload gcn_arxiv.w1

prints, before each of the tool's ``<train|eval> step, per chip`` lines,

    gather tables on chip: 20 of 20

(every row gather of the module: ``local_take``'s forward gathers, the
fused layer's backward gathers from the owner-side vertex tables, the
edge weights' row gathers, the halo exchange's send gathers), the same count
by the child scope that named each gather (``by child scope: rows 36 of 36,
send_gather 3 of 6``: the names the per-layer metrics ``gather_rows_ms.*`` and
``halo_send_gather_ms.train`` read in a device trace, docs/tracing.md) and
after them the ties ``map_vertex_chunks`` traced (``gather.chunks_sequenced``; it
ties where a table slice can be gathered from on-chip memory, whole or in
row parts), the row parts ``row_take`` cut its gathers into
(``gather.row_parts``) and the routes the fused layer's backward took
(``gather.bwd_transposed`` / ``gather.bwd_permuted`` of ``gather.bwd_chunks``:
the per-layer metric ``gather_bwd_transposed_pct.train``).
A table left in HBM is named with its size: until PR 33 the train step of
``gcn_arxiv.w1`` read ``4 of 8; in HBM: bf16[2332672,128] (597.2 MB)``,
the backward's gathers by ``halo_sort_perm`` out of an ``[E, C]`` edge
tensor that can never be placed; a ``bf16[2332672,128]`` there again means
a backward fell back to the permutation. ``gcn_papers100m.w4``'s forward
tables (``bf16[809728,128]``, 207.3 MB) fit no on-chip memory and are
gathered in two row parts since PR 35 (``bf16[404864,128]``, every one
placed: ``39 of 45`` in its train step, ``15 of 15`` in its eval step;
``gather.row_parts`` 36); a ``bf16[809728,128]`` named in HBM again means
the parts were not taken. A table on chip is worth 4.3 against 24.8 ms a
gather in ``gcn_arxiv.w1`` (PERF.md, PR 31 and PR 33). Compile only: not a
chip run, and no time comes from here.
"""

from __future__ import annotations

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


# the scopes a row gather runs under (``ops/local.py``, ``comm/collectives.py``)
CHILD = re.compile(r"/(rows|send_gather|scatter_add)/")


def by_child_line(gathers: list, compiled_text: str) -> str:
    """``placement_line``'s count, by the child scope in each gather's
    ``op_name`` or, for a gather that a ``jit`` of its own traced (its
    ``op_name`` is the bare primitive), in that of the fusion that calls its
    computation."""
    callers = {}
    for line in compiled_text.splitlines():
        called = re.search(r"calls=%?([\w.\-]+)", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if called and name:
            callers[called[1]] = name[1]
    groups = {}
    for g in gathers:
        m = CHILD.search(g["op_name"]) or CHILD.search(
            callers.get(g["computation"], ""))
        placed = groups.setdefault(m[1] if m else "(none)", [0, 0])
        placed[0] += g["memory_space"] != 0
        placed[1] += 1
    return "by child scope: " + ", ".join(
        f"{name} {on} of {n}" for name, (on, n) in sorted(groups.items()))


def main() -> int:
    from benchmark.tools import rehearse_w4  # sets the CPU platform first

    import jax

    from dgraph_tpu.analysis.hlo import gather_table_placement, placement_line
    from dgraph_tpu.obs.metrics import default_registry

    compile_lowered = jax.stages.Lowered.compile

    def compile_and_read(self, *args, **kwargs):
        compiled = compile_lowered(self, *args, **kwargs)
        text = compiled.as_text()
        gathers = gather_table_placement(text)
        print(placement_line(gathers), flush=True)
        print(by_child_line(gathers, text), flush=True)
        return compiled

    jax.stages.Lowered.compile = compile_and_read
    try:
        rc = rehearse_w4.main()
    finally:
        jax.stages.Lowered.compile = compile_lowered
    counters = default_registry.snapshot()["counters"]
    print(f"gather.chunks_sequenced: "
          f"{counters.get('gather.chunks_sequenced', 0):.0f} (both steps, "
          f"and the parameter init on the small graph); "
          f"gather.row_parts: {counters.get('gather.row_parts', 0):.0f}; "
          f"gather.bwd_transposed / gather.bwd_permuted: "
          f"{counters.get('gather.bwd_transposed', 0):.0f} / "
          f"{counters.get('gather.bwd_permuted', 0):.0f} of "
          f"gather.bwd_chunks {counters.get('gather.bwd_chunks', 0):.0f} "
          f"(the train step)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
