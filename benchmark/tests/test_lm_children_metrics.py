"""The eleven per-layer metrics that name what the sequence cells'
``*_other_ms.fed`` and ``exit_loss_ms.fed`` hold (ISSUE 51): declared with
their cells; their patterns run over ``data/lm_children_cut.trace.json.gz``,
recorded on the chip from that PR's tree (two steps of the builder's traced
run of ``kanana2_30b_a3b.seq16k``, cut as its siblings were: the operations
of 0.3 ms and more plus everything the ten ``scope_time`` metrics match,
``tf_op`` and ``hlo_category`` alone of each event's arguments); and over
the three traces recorded before the scopes existed, where every one of them
finds nothing and none raises."""

import functools

import pytest

from benchmark.reducers import scope_rest, scope_time
from benchmark.tests.test_rehearsal import BENCH
from benchmark.tests.test_sublayer_metrics import matched, record, spec

SEQ = ("ouro_2p6b.seq8k", "sdar_30b_a3b.bd8k", "lfm2_8b_a1b.seq16k",
       "phi4_mini_flash.seq8k", "nemotron3_nano_30b_a3b.seq8k",
       "smallthinker_21b_a3b.seq16k", "kanana2_30b_a3b.seq16k")
ROTATING = tuple(c for c in SEQ if not c.startswith(("phi4", "nemotron")))
ELSE, EXIT_LOSS = "everything else on the device", "exit loss"
# name -> (layer, workloads); every one ms, lower, device_trace, fed_step_ms
ELEVEN = {
    "lm_norm_ms.fed": (ELSE, SEQ),
    "lm_rotary_ms.fed": (ELSE, ROTATING),
    "lm_embed_ms.fed": (ELSE, SEQ),
    "lm_optimizer_ms.fed": (ELSE, SEQ),
    "lm_diff_ms.fed": ("sequence attention", ("phi4_mini_flash.seq8k",)),
    "lm_stream_ms.fed": ("looped stack", SEQ),
    "exit_loss_head_ms.fed": (EXIT_LOSS, SEQ),
    "exit_loss_softmax_ms.fed": (EXIT_LOSS, SEQ),
    "exit_loss_target_ms.fed": (EXIT_LOSS, SEQ),
    "exit_loss_rest_ms.fed": (EXIT_LOSS, SEQ),
    "lm_unnamed_ms.fed": (ELSE, SEQ),
}
HAD_BEFORE = 93  # per-layer entries of the benchmark these were appended to
SIX = tuple(ELEVEN)[:6]
EXIT = tuple(ELEVEN)[6:10]
REST = "lm_unnamed_ms.fed"
NOT_METRICS = ("lm_containers.fed", "lm_conditionals.fed")
OLD_TRACES = ("gcn_w1_cut.trace.json.gz", "gcn_w4_cut.trace.json.gz",
              "gcn_w4_children_cut.trace.json.gz")


@functools.lru_cache(None)
def run_of(file):
    return record(file)[0]


def read(run, name):
    reducer = {"scope_time": scope_time, "scope_rest": scope_rest}[
        spec(name)["reducer"]]
    return reducer.reduce(run, spec(name)["params"])


@pytest.fixture(scope="module")
def kanana():
    return run_of("lm_children_cut.trace.json.gz")


def test_the_eleven_metrics_are_appended_with_their_cells():
    got = {m["name"]: (m["layer"], tuple(m["workloads"]))
           for m in BENCH["per_layer"] if m["name"] in ELEVEN}
    assert got == ELEVEN
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[HAD_BEFORE:HAD_BEFORE + len(ELEVEN)] == list(ELEVEN)
    layers = {m["layer"] for m in BENCH["per_layer"][:HAD_BEFORE]}
    for m in BENCH["per_layer"][HAD_BEFORE:HAD_BEFORE + len(ELEVEN)]:
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "device_trace", "fed_step_ms")
        assert m["layer"] in layers  # a layer the benchmark already names
        assert spec(m["name"])["name"] == m["name"]
        assert spec(m["name"])["params"]["phase"] == "fed"
    # no reducer came with them
    assert {spec(n)["reducer"] for n in ELEVEN} == {"scope_time", "scope_rest"}
    assert {spec(n)["reducer"] for n in NOT_METRICS} == {"scope_time"}
    assert not {m["name"] for m in BENCH["per_layer"]} & set(NOT_METRICS)


def test_the_six_share_one_unless_list_and_exclude_each_other():
    """What the cells' ``*_other_ms.fed`` leave out, the six leave out: the
    attention scope, every mixer's scope, the exit loss, the dense products,
    the container events; then each leaves out the ones before it (diff,
    optimizer, embed, rotary, norm, stream): the outer scope keeps a nested
    operation, and nothing is under two names."""
    order = ("lm_diff_ms.fed", "lm_optimizer_ms.fed", "lm_embed_ms.fed",
             "lm_rotary_ms.fed", "lm_norm_ms.fed", "lm_stream_ms.fed")
    assert set(order) == set(SIX)
    shared = spec(order[0])["params"]["unless"]
    for name in ("attn_ms.fed", "lm_dense_ms.fed") + NOT_METRICS:
        assert set(spec(name)["params"]["match"]) <= set(shared)
    for scope in ("moe", "conv", "ssm", "ssd", "gmu", "mla_down", "mla_up",
                  "exit_loss"):
        assert any(scope in u for u in shared)
    before = []
    for name in order:
        params = spec(name)["params"]
        assert params["unless"] == shared + before, name
        before += params["match"]
    others = spec(REST)["params"]["others"]
    assert set(SIX + EXIT + NOT_METRICS) <= set(others)
    # every accepted ``*_other_ms.fed`` sibling of the sequence cells is
    # among the eleventh's siblings under its own or a wider pattern
    for cell_other in ("lm", "sdar", "lfm2", "phi4", "nemotron",
                       "smallthinker", "kanana"):
        for sibling in spec(f"{cell_other}_other_ms.fed")["params"]["others"]:
            assert sibling in others or sibling.endswith("_dense_ms.fed")


def test_every_operation_is_under_exactly_one_name(kanana):
    """On the recorded steps: an operation is matched by at most one of the
    ten ``scope_time`` metrics; one of the six is under no accepted sibling;
    one of the exit loss's four is ``exit_loss_ms.fed``'s alone, and the
    four leave nothing of it; what no name matches is the eleventh's or a
    container."""
    others = spec(REST)["params"]["others"]
    accepted = [n for n in others if n not in SIX + EXIT + NOT_METRICS]
    new = {n: set(matched(kanana, n)) for n in SIX + EXIT}
    old = {n: set(matched(kanana, n)) for n in accepted}
    containers = {i for n in NOT_METRICS for i in matched(kanana, n)}
    ops = [o for d in kanana.trace.devices.values() for o in d]
    assert ops
    for o in ops:
        mine = [n for n, ids in new.items() if id(o) in ids]
        theirs = [n for n, ids in old.items() if id(o) in ids]
        assert len(mine) <= 1, (mine, o.scope)
        if mine and mine[0] in SIX:
            assert not theirs and id(o) not in containers, (mine, o.scope)
        elif mine:
            assert theirs == ["exit_loss_ms.fed"], (mine, theirs, o.scope)
        else:
            assert "exit_loss_ms.fed" not in theirs, o.scope
    here = set(SIX + EXIT) - {"lm_diff_ms.fed"}  # no difference in this stack
    assert {n for n, ids in new.items() if ids} == here


def test_the_sums_close(kanana):
    """The six, the eleventh and the ``conditional`` containers add up to
    the cell's ``kanana_other_ms.fed``; the four add up to
    ``exit_loss_ms.fed``."""
    value = lambda n: read(kanana, n) or 0.0  # noqa: E731
    conditionals = value("lm_conditionals.fed")
    assert conditionals > 0  # the expert ladder's
    parts = sum(value(n) for n in SIX + (REST,)) + conditionals
    assert parts == pytest.approx(value("kanana_other_ms.fed"), abs=1e-6)
    assert sum(value(n) for n in EXIT) == pytest.approx(
        value("exit_loss_ms.fed"), abs=1e-6)
    assert value("lm_diff_ms.fed") == 0.0 and read(
        kanana, "lm_diff_ms.fed") is None


# what the builder's traced run the cut was made from read over its five
# steps (seed 5100000101; PERF.md section 5); the cut keeps two of them
RECORDED = {
    "lm_norm_ms.fed": 5.0876, "lm_rotary_ms.fed": 90.3771,
    "lm_embed_ms.fed": 2.2938, "lm_optimizer_ms.fed": 14.5215,
    "lm_stream_ms.fed": 12.3001, "exit_loss_head_ms.fed": 19.3556,
    "exit_loss_softmax_ms.fed": 1.3961, "exit_loss_target_ms.fed": 0.2536,
    "exit_loss_rest_ms.fed": 0.3643,
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_trace_reads_what_the_chip_run_read(kanana, name):
    """Everything under the scopes is kept, so the cut reads the run's
    values; the embedding's ``scatter-add`` follows the step's token ids
    (2.22 ms over the two steps kept, 2.29 over the five)."""
    assert read(kanana, name) == pytest.approx(
        RECORDED[name], rel=5e-2 if "embed" in name else 2e-3)


@pytest.mark.parametrize("file", OLD_TRACES)
@pytest.mark.parametrize("name", sorted(ELEVEN))
def test_old_traces_give_nothing_and_do_not_raise(file, name):
    """Recorded from graph cells, before the scopes existed: no ``fed``
    phase, no ``dgraph.lm.*`` path. Every one of the eleven reads nothing."""
    assert read(run_of(file), name) is None
