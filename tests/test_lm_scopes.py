"""Every device operation of the sequence LM's train step lies under one
``dgraph.lm.*`` scope, and each scope has the per-layer metric that reads it
(ISSUE 51): ``dgraph.lm.norm``, ``dgraph.lm.embed``, ``dgraph.lm.rotary``,
``dgraph.lm.optimizer``, ``dgraph.lm.diff``, what ``dgraph.lm.loop_pass``
holds outside every child (the stream), and under ``dgraph.lm.exit_loss`` the
head, the cross-entropy and its ``target_logit``. The benchmark's metrics
(``benchmark/layer_metrics/lm_*_ms.fed.json``, ``exit_loss_*_ms.fed.json``)
match these names in a device trace's operation paths, so each is looked for
where a trace would carry it: the location of an operation in the lowered
train step of the seven tiny presets the model tests build. The patterns are
read from the metrics' own files."""

import functools
import json
import os
import re
import types

import pytest

import jax
import jax.numpy as jnp
import optax

from dgraph_tpu.train import lm

from test_lfm2 import earlier_tiny  # Ouro's, SDAR's and LFM2's tiny presets

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRESETS = ("ouro", "sdar", "lfm2", "phi4flash", "nemotron_h", "smallthinker",
           "kanana")
CONFIGS = {"phi4flash": "phi4_mini_flash",
           "nemotron_h": "nemotron3_nano_30b_a3b",
           "smallthinker": "smallthinker_21b_a3b", "kanana": "kanana2_30b_a3b"}
# the six that cut what the cells' ``*_other_ms.fed`` hold, the four that cut
# ``exit_loss_ms.fed``, and what none of them and no accepted metric names
SIX = ("lm_norm_ms.fed", "lm_rotary_ms.fed", "lm_embed_ms.fed",
       "lm_optimizer_ms.fed", "lm_diff_ms.fed", "lm_stream_ms.fed")
EXIT = ("exit_loss_head_ms.fed", "exit_loss_softmax_ms.fed",
        "exit_loss_target_ms.fed", "exit_loss_rest_ms.fed")
REST = "lm_unnamed_ms.fed"
NOT_METRICS = ("lm_containers.fed", "lm_conditionals.fed")
ROTATING = ("ouro", "sdar", "lfm2", "smallthinker", "kanana")
NOT_OPERATIONS = ("func.return", "stablehlo.return", "return")


def spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def matcher(name):
    from benchmark.reducers import scope_time

    hit = scope_time.matcher(spec(name)["params"])
    return lambda op, path: hit(types.SimpleNamespace(
        scope=path, name=op, category=""))


def located_ops(text) -> list:
    """``[(operation, elements of its largest result, named-scope path)]`` of
    a lowered module (``as_text(debug_info=True)``). An operation inside an
    outlined function carries a path relative to that function; XLA's call
    inliner prefixes the call's, and so does this (one entry a call site)."""
    names = {}
    for ref, body in re.findall(r'^(#loc\d+) = loc\((.*)\)$', text, re.M):
        m = re.match(r'"([^"]*)"(?!:)', body)  # a name, not a file location
        if m:
            names[ref] = m.group(1)
    def elements(types_):
        types_ = types_.rsplit("->", 1)[-1] if "->" in types_ \
            else types_.rsplit(" : ", 1)[-1]
        return max((functools.reduce(
            lambda n, d: n * (int(d) if d.isdigit() else 1),
            t.split("x")[:-1], 1)
            for t in re.findall(r'tensor<([^>]*)>', types_)), default=0)

    funcs, calls, current, open_ops = {}, {}, None, []
    for line in text.splitlines():
        head = re.match(r'\s*func\.func \w+ @([\w\.]+)\(', line)
        if head:
            current, open_ops = head.group(1), []
            funcs[current] = []
            continue
        if current is None or line.lstrip().startswith("#loc"):
            continue
        m = re.search(r' loc\((#loc\d+)\)\s*$', line)
        body = line[:m.start()] if m else line
        op = re.match(r'\s*(?:%[\w#:]+ = )?"?([\w\.]+)"?', body)
        if not m:  # an operation with regions: its location closes it
            if op and body.rstrip().endswith("{"):
                open_ops.append((op.group(1), body))
            continue
        path = names.get(m.group(1), "")
        if body.lstrip().startswith("}"):
            if open_ops:
                name, header = open_ops.pop()
                funcs[current].append((name, max(elements(body),
                                                 elements(header)), path))
            continue
        if not op:
            continue
        callee = re.search(r'\bcall @([\w\.]+)\(', body)
        if callee:
            calls.setdefault(callee.group(1), []).append((current, path))
            continue
        funcs[current].append((op.group(1), elements(body), path))

    @functools.lru_cache(None)
    def prefixes(fn):
        if fn == "main" or fn not in calls:
            return ("",)
        return tuple(sorted({(p + "/" if p else "") + path
                             for caller, path in calls[fn]
                             for p in prefixes(caller)}))

    return [(op, n, (pre + "/" if pre else "") + path)
            for fn, ops in funcs.items() for pre in prefixes(fn)
            for op, n, path in ops if op not in NOT_OPERATIONS]


def tiny_model(preset):
    """(model, tokens of a step, the step's keywords) of a tiny preset."""
    if preset in CONFIGS:
        import importlib

        with open(os.path.join(ROOT, "benchmark", "configs",
                               CONFIGS[preset] + ".json")) as f:
            size = json.load(f)["tiny"]
        model = importlib.import_module(
            f"benchmark.builders.{preset}").model_of(size, lm.lm_comm(1))
        return model, size["seq_len"], {}
    _, model, seq_len, kw, _ = earlier_tiny()[PRESETS.index(preset)]
    return model, seq_len, kw


def batch_of(model, T):
    tokens = jax.ShapeDtypeStruct((T,), jnp.int32)
    if not model.block_length:
        return tokens
    return (tokens, jax.ShapeDtypeStruct((T,), jnp.bool_),
            jax.ShapeDtypeStruct((T,), jnp.float32))


def shapes_of(model):
    return jax.eval_shape(lambda t: model.init(
        jax.random.key(0), *lm._probe_rows(model, t)),
        jnp.zeros((lm.INIT_PROBE_TOKENS,), jnp.int32))


@functools.lru_cache(None)
def train_step_ops(preset):
    """(the located operations of the preset's lowered train step, the
    elements of one ``[T, hidden]`` tensor)."""
    model, T, kw = tiny_model(preset)
    opt = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    mesh = lm.lm_mesh(1)
    params = shapes_of(model)
    step = lm.make_lm_train_step(model, opt, mesh, model.comm, seq_len=T, **kw)
    with jax.set_mesh(mesh):
        low = step.lower(params, jax.eval_shape(opt.init, params),
                         batch_of(model, T))
    return located_ops(low.as_text(debug_info=True)), T * model.hidden_size


def found(ops, pattern, backward=None):
    return [p for _, _, p in ops if re.search(pattern, p)
            and (backward is None or ("transpose(" in p) == backward)]


@pytest.mark.parametrize("preset", PRESETS)
def test_every_scope_occurs_where_the_stack_has_the_work(preset):
    """Forward and under ``transpose(``: a norm, the embedding (its backward
    is the ``scatter-add`` into the table), the rotary embedding with its
    tables where the stack takes positions, the differential attention's
    difference where the stack has one, the exit loss's head and
    cross-entropy with the target's logit; the optimizer (no transpose: it
    is not differentiated) with the masked update of the buffers."""
    ops, _ = train_step_ops(preset)
    both = [r"dgraph\.lm\.norm", r"dgraph\.lm\.embed",
            r"dgraph\.lm\.exit_loss.*dgraph\.lm\.head",
            r"dgraph\.lm\.exit_loss.*dgraph\.lm\.cross_entropy",
            r"dgraph\.lm\.cross_entropy.*/target_logit/"]
    if preset in ROTATING:
        both.append(r"dgraph\.lm\.rotary")
    if preset == "phi4flash":
        both.append(r"dgraph\.lm\.diff")
    for pattern in both:
        assert found(ops, pattern, backward=False), pattern
        assert found(ops, pattern, backward=True), pattern
    if preset not in ROTATING:
        assert not found(ops, r"dgraph\.lm\.rotary")
    assert found(ops, r"dgraph\.lm\.optimizer", backward=False)
    assert not found(ops, r"dgraph\.lm\.optimizer", backward=True)
    # the embedding's backward, under the program's name
    assert found(ops, r"dgraph\.lm\.embed.*scatter-add$", backward=True)
    # the tables are built under the scope, not only applied
    if preset in ROTATING:
        assert found(ops, r"dgraph\.lm\.rotary/(cos|sin)$")
    if preset == "kanana":  # the slice of q's rotary part and its way back
        assert found(ops, r"attend_latent/dgraph\.lm\.rotary/(slice|concatenate)$")
        # the key's side stays the up-projection's
        assert found(ops, r"dgraph\.lm\.mla_up/dgraph\.lm\.rotary")
    if preset == "phi4flash":  # the sub-norm is the difference's
        assert found(ops, r"dgraph\.lm\.diff/subln/dgraph\.lm\.norm")


@pytest.mark.parametrize("preset", PRESETS)
def test_no_operation_is_under_two_of_the_new_metrics(preset):
    """With the metrics' own patterns: an operation is matched by at most one
    of the ten ``scope_time`` metrics; one of the six is under no accepted
    metric of the sequence cells; one of the exit loss's four is
    ``exit_loss_ms.fed``'s and no other's; the eleventh reads what is left."""
    ops, _ = train_step_ops(preset)
    others = spec(REST)["params"]["others"]
    assert set(SIX + EXIT + NOT_METRICS) <= set(others)
    accepted = [n for n in others if n not in SIX + EXIT + NOT_METRICS]
    new = {n: matcher(n) for n in SIX + EXIT}
    old = {n: matcher(n) for n in accepted}
    counts = dict.fromkeys(SIX + EXIT + (REST,), 0)
    for op, _, path in ops:
        mine = [n for n, hit in new.items() if hit(op, path)]
        theirs = [n for n, hit in old.items() if hit(op, path)]
        assert len(mine) <= 1, (mine, path)
        if mine and mine[0] in SIX:
            assert not theirs, (mine, theirs, path)
        elif mine:
            assert theirs == ["exit_loss_ms.fed"], (mine, theirs, path)
        else:  # the exit loss is cut whole: nothing of it is left over
            assert "exit_loss_ms.fed" not in theirs, path
        if mine or not theirs:
            counts[mine[0] if mine else REST] += 1
    here = set(SIX + EXIT) - {"lm_diff_ms.fed", "lm_rotary_ms.fed"}
    here |= {"lm_rotary_ms.fed"} if preset in ROTATING else set()
    here |= {"lm_diff_ms.fed"} if preset == "phi4flash" else set()
    assert {n for n in SIX + EXIT if counts[n]} == here, counts


@pytest.mark.parametrize("preset", PRESETS)
def test_every_large_operation_lies_under_a_scope_of_the_programs(preset):
    """The guard that keeps later layers named: an operation whose result
    holds ``T x hidden`` elements or more lies under some ``dgraph.lm.*`` or
    ``dgraph.comm.*`` scope. A layer added without one fails here, before a
    chip run reads it as ``lm_unnamed_ms.fed``."""
    ops, large = train_step_ops(preset)
    bare = sorted({(op, path) for op, n, path in ops if n >= large
                   and not re.search(r"dgraph\.(lm|comm)\.", path)})
    assert not bare, bare[:10]


@pytest.mark.parametrize("differentiated", [False, True],
                         ids=["forward", "differentiated"])
def test_the_targets_logit_is_named_in_both_losses(differentiated):
    """``weighted_cross_entropy`` is ``lax.map`` over the blocks forward only
    and one loop with each block's pull-back where it is differentiated: the
    gather of the target's logit is under ``target_logit`` in both, and its
    transpose in the second."""
    model, T, kw = tiny_model("ouro")
    mesh = lm.lm_mesh(1)
    loss = lm.make_lm_loss(model, mesh, model.comm, seq_len=T, **kw)
    fn = jax.grad(loss) if differentiated else loss
    with jax.set_mesh(mesh):
        ops = located_ops(jax.jit(fn).lower(
            shapes_of(model), batch_of(model, T)).as_text(debug_info=True))
    target = r"dgraph\.lm\.exit_loss.*dgraph\.lm\.cross_entropy.*/target_logit/"
    assert found(ops, target + r".*gather$", backward=False)
    assert bool(found(ops, target, backward=True)) == differentiated
    assert spec("exit_loss_target_ms.fed")["params"]["match"] == [target]
