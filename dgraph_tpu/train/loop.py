"""SPMD training loop for full-graph node-level tasks.

The reference keeps its training loops in experiment scripts
(``experiments/OGB/main.py:50-227``) with DDP for gradient sync; here the
loop is a library: one jitted train step that runs the whole
model + loss + backward + gradient psum under ``shard_map`` over the
``('replica','graph')`` mesh, with optax for updates. Loss is normalized by
the *global* target count, matching the reference
(``distributed_layers.py:210-214``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from dgraph_tpu.comm.mesh import (
    GRAPH_AXIS,
    REPLICA_AXIS,
    plan_in_specs,
    put_on_graph_axis,
    squeeze_plan,
    tree_size,
)
from dgraph_tpu.obs.metrics import StepMetrics
from dgraph_tpu.plan import EdgePlan


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: int = 0


def init_params(model, mesh, plan: EdgePlan, batch: dict, seed: int = 0,
                batch_args: Callable = None):
    """Initialize params under shard_map (the model's collectives need the
    mesh axis bound even at trace time). Same key on every shard ->
    deterministic identical params, declared replicated via out_specs P()."""
    from dgraph_tpu.comm.collectives import shard_map_checks

    batch_args = batch_args or _batch_args

    def body(batch_, plan_):
        plan_s = squeeze_plan(plan_)
        b = jax.tree.map(lambda leaf: leaf[0], batch_)
        return model.init(jax.random.key(seed), *batch_args(b, plan_s))

    batch_specs = jax.tree.map(lambda _: P(GRAPH_AXIS), batch)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(batch_specs, plan_in_specs(plan)),
        out_specs=P(),
        **shard_map_checks(relax="init outputs replicated by construction"),
    )
    from dgraph_tpu.obs import spans

    # always-on stage: what the host does inside the call (trace, compile,
    # issue); the device's part is not waited for
    with spans.stage("setup.init_params") as st, jax.set_mesh(mesh):
        params = jax.jit(fn)(batch, plan)
        st.annotate(**tree_size(params))
        return params


def init_opt_state(optimizer: optax.GradientTransformation, params, mesh):
    """``optimizer.init(params)`` under the mesh, so its step counter gets
    the same (mesh-typed) aval the train step returns — initialised outside
    it, step 1 sees a new input type, retraces and compiles again."""
    from dgraph_tpu.obs import spans

    with spans.stage("setup.init_opt_state", **tree_size(params)), \
            jax.set_mesh(mesh):
        return optimizer.init(params)


def masked_cross_entropy(logits, labels, mask, axis_name):
    """Sum of per-vertex CE over the mask / global mask count."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ll = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=1)[:, 0]
    local = -(ll * mask).sum()
    count = mask.sum()
    if axis_name is not None:
        count = lax.psum(count, axis_name)
    return local / jnp.maximum(count, 1.0)


def masked_bce_multilabel(logits, labels, mask, axis_name):
    """Mean sigmoid BCE for [n, C] multi-label float targets (ogbn-proteins'
    112-way labels — the case the reference handles with a per-dataset
    num_classes table, ``ogbn_datasets.py:25-37``)."""
    logits = logits.astype(jnp.float32)
    labels = labels.astype(jnp.float32)
    per = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    local = (per.sum(axis=-1) * mask).sum()
    count = mask.sum() * logits.shape[-1]
    if axis_name is not None:
        count = lax.psum(count, axis_name)
    return local / jnp.maximum(count, 1.0)


def _batch_args(b: dict, plan):
    """Default model-arg builder: (x, plan, [edge_weight]) — the GCN-family
    signature. Models with other signatures (e.g. GraphTransformer's
    (x, plan, vmask)) pass a custom ``batch_args`` to the step builders /
    ``fit``."""
    args = [b["x"], plan]
    if "edge_weight" in b:
        args.append(b["edge_weight"])
    return args


def vmask_batch_args(b: dict, plan):
    """(x, plan, vmask) — the GraphTransformer signature (global-attention
    models need the vertex padding mask, not edge weights)."""
    return [b["x"], plan, b["vmask"]]


def model_apply(model, params, b: dict, plan, batch_args: Callable = None):
    """THE per-shard forward call: train, eval, and serve all route the
    model through this one helper (``model.apply(params, *batch_args(b,
    plan))``), so the forward semantics — which batch keys feed which model
    arguments — cannot drift between the three paths. ``b`` and ``plan``
    are per-shard (already squeezed); ``batch_args`` defaults to the
    GCN-family ``(x, plan, [edge_weight])`` builder."""
    batch_args = batch_args or _batch_args
    return model.apply(params, *batch_args(b, plan))


def make_train_step(
    model,
    optimizer: optax.GradientTransformation,
    mesh,
    plan_template: EdgePlan,
    *,
    loss_fn: Callable = masked_cross_entropy,
    donate: bool = True,
    per_replica_batch: bool = False,
    batch_args: Callable = None,
    step_metrics: bool = False,
    nonfinite_guard: bool = False,
):
    """Build a jitted SPMD train step: (params, opt_state, batch, plan) ->
    (params, opt_state, metrics).

    ``step_metrics=True`` returns a :class:`~dgraph_tpu.obs.metrics.
    StepMetrics` aux-pytree (loss, accuracy, grad_norm, mask_count) instead
    of the bare dict; the flag is a BUILD-time constant, so the default
    step's traced program is byte-identical to the flag not existing —
    zero overhead and zero extra recompiles when disabled (pinned by
    tests/test_obs.py).

    ``nonfinite_guard=True`` adds an all-finite check on the global grad
    norm and selects — via ``jnp.where`` inside the SAME traced program,
    so a poisoned step and a clean step replay one executable with zero
    recompiles (pinned by tests/test_obs.py) — between the applied update
    and the carried-forward ``(params, opt_state)``.  The skip indicator
    comes back in the metrics as ``nonfinite_skipped`` (0.0/1.0); feed it
    to :class:`~dgraph_tpu.train.guard.NonFiniteMonitor` to abort after N
    consecutive skips.  Like ``step_metrics`` this is a build-time
    constant: disabled, the traced program is byte-identical to the flag
    not existing.

    ``batch`` is a dict pytree with leading-[W] leaves (from
    ``DistributedGraph.batch`` + labels); params/opt_state are replicated.

    ``per_replica_batch=True``: batch leaves carry a leading [R, W, ...]
    pair of axes and each replica group trains on its OWN sample (see
    :class:`~dgraph_tpu.train.sampler.ReplicaSampler` — the reference's
    ``CommAwareDistributedSampler`` semantics, ``dist_utils.py:50-113``).
    With False (default), all replicas see the same batch and data
    parallelism degenerates to scaled-loss replication.
    """

    # replica-axis size (data parallelism): grads auto-psum over EVERY axis
    # params are replicated on, so scale the loss by 1/num_replicas to turn
    # the replica-sum into the DDP mean (graph-axis contributions are partial
    # sums of one sample and must stay a sum).
    num_replicas = dict(mesh.shape).get(REPLICA_AXIS, 1)
    batch_args = batch_args or _batch_args
    batch_spec = (
        P(REPLICA_AXIS, GRAPH_AXIS) if per_replica_batch else P(GRAPH_AXIS)
    )

    def _squeeze_batch(batch):
        # drop the size-1 per-shard leading axes shard_map leaves on each
        # leaf: [1, n, ...] (shared batch) or [1, 1, n, ...] (per-replica)
        n_lead = 2 if per_replica_batch else 1
        out = batch
        for _ in range(n_lead):
            out = jax.tree.map(lambda leaf: leaf[0], out)
        return out

    def shard_body(params, batch, plan):
        plan = squeeze_plan(plan)
        b = _squeeze_batch(batch)

        def lf(p):
            logits = model_apply(model, p, b, plan, batch_args)
            loss = loss_fn(logits, b["y"], b["mask"], GRAPH_AXIS)
            if b["y"].ndim == logits.ndim:
                # multi-label float targets: per-label binary accuracy
                hits = ((logits > 0) == (b["y"] > 0.5)).mean(axis=-1)
                correct = (hits * b["mask"]).sum()
            else:
                correct = ((jnp.argmax(logits, -1) == b["y"]) * b["mask"]).sum()
            return loss / num_replicas, (loss, correct)

        (_, (loss, correct)), grads = jax.value_and_grad(lf, has_aux=True)(params)
        # NO explicit grad psum: params enter replicated (in_specs P()),
        # and shard_map's vma tracking makes grad-of-replicated-input
        # insert the cross-shard psum automatically (the transpose of the
        # replicated broadcast) over BOTH axes — an extra lax.psum here
        # would double-count by W. With the loss pre-scaled by
        # 1/num_replicas the replica psum is exactly the DDP mean. Pinned
        # by tests/test_models.py::test_distributed_gradients_match_single_
        # device.
        loss = lax.psum(loss, GRAPH_AXIS)
        mask_count = lax.psum(b["mask"].sum(), GRAPH_AXIS)
        acc = lax.psum(correct, GRAPH_AXIS) / jnp.maximum(mask_count, 1.0)
        if per_replica_batch:
            # distinct samples: report the replica-mean metrics (out_specs
            # P() requires values statically replicated over the replica
            # axis — also when its size is 1)
            loss = lax.pmean(loss, REPLICA_AXIS)
            acc = lax.pmean(acc, REPLICA_AXIS)
            mask_count = lax.pmean(mask_count, REPLICA_AXIS)
        out = {"loss": loss, "accuracy": acc}
        if step_metrics:
            out["mask_count"] = mask_count
        return grads, out

    def step(params, opt_state, batch, plan):
        from dgraph_tpu.comm.collectives import shard_map_checks

        batch_specs = jax.tree.map(lambda _: batch_spec, batch)
        grads, metrics = jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(P(), batch_specs, plan_in_specs(plan)),
            out_specs=(P(), P()),
            **shard_map_checks(plan, GRAPH_AXIS),
        )(params, batch, plan)
        if nonfinite_guard:
            # one scalar decides the whole step: a single non-finite value
            # anywhere in the grads makes the global norm non-finite, and
            # applying such an update would poison params forever. The
            # select is data-dependent inside the one traced program —
            # skipped and applied steps share the executable.
            gnorm = optax.global_norm(grads)
            ok = jnp.isfinite(gnorm)
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            params = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_params, params
            )
            opt_state = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_opt_state, opt_state
            )
            skipped = 1.0 - ok.astype(jnp.float32)
        else:
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        if step_metrics:
            metrics = StepMetrics(
                loss=metrics["loss"],
                accuracy=metrics["accuracy"],
                grad_norm=gnorm if nonfinite_guard else optax.global_norm(grads),
                mask_count=metrics["mask_count"],
                nonfinite_skipped=skipped if nonfinite_guard else None,
            )
        elif nonfinite_guard:
            metrics = dict(metrics, nonfinite_skipped=skipped)
        return params, opt_state, metrics

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def make_eval_step(model, mesh, loss_fn: Callable = masked_cross_entropy,
                   batch_args: Callable = None):
    """Jitted SPMD eval: (params, batch, plan) -> metrics dict."""
    batch_args = batch_args or _batch_args

    def shard_body(params, batch, plan):
        plan = squeeze_plan(plan)
        b = jax.tree.map(lambda leaf: leaf[0], batch)
        logits = model_apply(model, params, b, plan, batch_args)
        loss = loss_fn(logits, b["y"], b["mask"], GRAPH_AXIS)
        if b["y"].ndim == logits.ndim:
            hits = ((logits > 0) == (b["y"] > 0.5)).mean(axis=-1)
            correct = (hits * b["mask"]).sum()
        else:
            correct = ((jnp.argmax(logits, -1) == b["y"]) * b["mask"]).sum()
        acc = lax.psum(correct, GRAPH_AXIS) / jnp.maximum(
            lax.psum(b["mask"].sum(), GRAPH_AXIS), 1.0
        )
        return {"loss": lax.psum(loss, GRAPH_AXIS), "accuracy": acc}

    def step(params, batch, plan):
        from dgraph_tpu.comm.collectives import shard_map_checks

        batch_specs = jax.tree.map(lambda _: P(GRAPH_AXIS), batch)
        return jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(P(), batch_specs, plan_in_specs(plan)),
            out_specs=P(),
            **shard_map_checks(plan, GRAPH_AXIS),
        )(params, batch, plan)

    return jax.jit(step)


def fit(
    model,
    graph,
    mesh,
    *,
    optimizer: Optional[optax.GradientTransformation] = None,
    num_epochs: int = 50,
    seed: int = 0,
    log_every: int = 0,
    loss_fn: Callable = masked_cross_entropy,
    batch_args: Callable = None,
    nonfinite_guard: bool = False,
):
    """Convenience full-graph training driver (the ``_run_experiment`` loop,
    ``experiments/OGB/main.py:50-227``, as a function). Returns
    (params, history).

    This loop owns the per-epoch batch, so it is also the in-repo consumer
    of the ``grads`` chaos point (:mod:`dgraph_tpu.chaos`): a
    ``grads=poison@K`` clause NaN-poisons epoch K's features host-side,
    which makes that step's gradients non-finite — pair it with
    ``nonfinite_guard=True`` to watch the guard absorb it."""
    import numpy as np

    from dgraph_tpu import chaos
    from dgraph_tpu.obs import spans
    from dgraph_tpu.utils.compile_cache import compile_totals

    optimizer = optimizer or optax.adam(1e-2)
    # vmask rides along for models whose batch_args want it (harmless
    # otherwise — the default builder ignores unknown keys)
    batch_tr = dict(graph.batch("train"), y=graph.labels, vmask=graph.vertex_mask)
    batch_va = dict(graph.batch("val"), y=graph.labels, vmask=graph.vertex_mask)
    batch_tr = put_on_graph_axis(batch_tr, mesh)
    batch_va = put_on_graph_axis(batch_va, mesh)
    plan = put_on_graph_axis(graph.plan, mesh)

    params = init_params(model, mesh, plan, batch_tr, seed, batch_args=batch_args)
    opt_state = init_opt_state(optimizer, params, mesh)
    train_step = make_train_step(
        model, optimizer, mesh, plan, loss_fn=loss_fn, batch_args=batch_args,
        nonfinite_guard=nonfinite_guard,
    )
    eval_step = make_eval_step(model, mesh, loss_fn=loss_fn, batch_args=batch_args)

    def compiles() -> float:
        # 0 where enable_compile_cache was never called: nothing is reported
        return compile_totals().get("compile.count", 0.0)

    history = []
    with jax.set_mesh(mesh):
        for epoch in range(num_epochs):
            bt = batch_tr
            if chaos.fire("grads", index=epoch):
                # host-side poison of this epoch's features only — same
                # shapes, same executable, one step's grads go non-finite
                # (a plain device_put: setup.place is once a launch)
                bt = dict(batch_tr, x=jax.device_put(
                    chaos.poison_array(batch_tr["x"]), batch_tr["x"].sharding))
            if epoch == 2:
                compiled = compiles()
            # host-boundary spans (never inside the jitted step), one attr
            # read each when tracing is off: train.step ends once the loss
            # is on the host; its children are the dispatch and the wait
            with spans.span("train.step", epoch=epoch):
                with spans.span("step_dispatch"):
                    params, opt_state, m = train_step(
                        params, opt_state, bt, plan)
                with spans.span("block"):
                    rec = {"epoch": epoch, "loss": float(m["loss"]),
                           "acc": float(m["accuracy"])}
            if log_every and epoch % log_every == 0:
                with spans.span("train.eval", epoch=epoch):
                    with spans.span("step_dispatch"):
                        ev = eval_step(params, batch_va, plan)
                    with spans.span("block"):
                        rec["val_loss"] = float(ev["loss"])
                        rec["val_acc"] = float(ev["accuracy"])
                print(rec)
            history.append(rec)
        # every shape was seen by the end of the second epoch (train and,
        # at epoch 0, eval): a compile after it is a retrace
        if num_epochs > 2 and compiles() > compiled:
            spans.span("train.recompile", compiles=compiles() - compiled,
                       after_epoch=2).end()
    return params, history
