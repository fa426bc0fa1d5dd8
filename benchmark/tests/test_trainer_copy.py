"""``builders/graphcast.py`` times a copy of the step and the loop of
``experiments/graphcast_train.py``, which sit inside that script's ``main``
and cannot be imported. These tests fail when the trainer's side moves, so
that the copy is brought after it (and the pins below with it): until the
program makes its step importable, a change to the trainer's step or loop
shows in ``fed_step_ms`` only through the copy."""

import ast
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def tree_of(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return ast.parse(f.read())


TRAINER = tree_of("experiments", "graphcast_train.py")
COPY = tree_of("benchmark", "builders", "graphcast.py")


def function(tree, name):
    found = [n for n in ast.walk(tree)
             if isinstance(n, ast.FunctionDef) and n.name == name]
    assert len(found) == 1, f"{name}: {len(found)} definitions"
    return found[0]


def assignment(tree, target):
    found = [n for n in ast.walk(tree) if isinstance(n, ast.Assign)
             and ast.unparse(n.targets[0]) == target]
    assert len(found) == 1, f"{target}: {len(found)} assignments"
    return found[0]


def test_loss_and_gradient_body_is_the_trainers():
    assert ast.unparse(function(COPY, "train_body")) \
        == ast.unparse(function(TRAINER, "train_body"))
    assert ast.unparse(assignment(COPY, "body")) \
        == ast.unparse(assignment(TRAINER, "body"))


# the trainer's jitted step as the copy was taken from it; the copy is this
# with the trainer's defaults put in (step_metrics off, the EMA track on)
TRAINER_STEP = '''\
@jax.jit
def step(params, opt_state, ema, x, y):
    loss, grads = body(params, x, y, gmask, statics, plans)
    gn = optax.global_norm(grads) if cfg.step_metrics else None
    updates, opt_state = opt.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    if ema is not None:
        ema = ema_update(ema, params, cfg.ema_decay)
    return (params, opt_state, ema, StepMetrics(loss=loss, grad_norm=gn))'''
COPY_STEP = '''\
@jax.jit
def step(params, opt_state, ema, x, y):
    loss, grads = body(params, x, y, gmask, statics, plans)
    updates, opt_state = opt.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    ema = ema_update(ema, params, ema_decay)
    return (params, opt_state, ema, StepMetrics(loss=loss, grad_norm=None))'''
# the trainer's loop from the batch to the block: what one timed step repeats
TRAINER_LOOP = '''\
x, y = ds.get_sharded(step_idx)
t0 = time.perf_counter()
params, opt_state, ema, sm = step(params, opt_state, ema, jnp.asarray(x), jnp.asarray(y))
jax.block_until_ready(sm.loss)'''
COPY_LOOP_CALLS = ["jnp.asarray", "jnp.asarray", "self.step",
                   "jax.block_until_ready"]


def test_jitted_step_is_the_trainers():
    assert ast.unparse(function(TRAINER, "step")) == TRAINER_STEP, \
        "the trainer's step changed: bring builders/graphcast.py after it"
    assert ast.unparse(function(COPY, "step")) == COPY_STEP


def test_timed_loop_is_the_trainers():
    loop = [n for n in ast.walk(TRAINER) if isinstance(n, ast.While)
            and ast.unparse(n.test) == "step_idx < cfg.steps"]
    assert len(loop) == 1
    assert "\n".join(ast.unparse(s) for s in loop[0].body[:4]) == TRAINER_LOOP, \
        "the trainer's loop changed: bring GraphCastCell.fed_once after it"
    calls = sorted(
        (n for n in ast.walk(function(COPY, "fed_once"))
         if isinstance(n, ast.Call)),
        key=lambda n: (n.lineno, n.col_offset))
    names = [ast.unparse(n.func) for n in calls]
    assert [n for n in names if n in set(COPY_LOOP_CALLS)] == COPY_LOOP_CALLS
