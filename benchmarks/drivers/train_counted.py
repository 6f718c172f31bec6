"""The loop for cells of kind ``train_counted``: ``drivers/train.py``'s loop, with
two things the cell's family brings.

Its counts: the step's operations and bytes by ``train_flops``,
``attention_train_flops``, ``attention_train_bytes`` and ``scan_train_bytes``, not
by ``benchmarks/flops.py``, whose formulas are a dense GPT-2 block's (attention in
every layer, two MLP matmuls, full-width k and v).

Its check of the backward: the first call of the cell's step (the program that is
then timed) leaves adam's first moment in the optimizer state, which after one
step is ``(1 - b1)`` times the gradient. For the layers the family watches
(``watched_layers``) it is compared, leaf by leaf, with the same optimizer's first
moment of the plain reference's float32 gradient (``reference_layer_grads``) on the
same weights and batch: ``|program - reference| / |reference|`` in the 2-norm, of
every leaf that is not tiny; a state left unchanged reads 1. ``correct`` needs every leaf within the traffic
file's ``check.first_moment_tolerance``. The loss at initial weights cannot see a
lower precision, nor anything of the backward (PERF.md §4).

A shim: ``train.py`` has no seam for either, so the step is wrapped where
``train.build_step`` hands it over. ROADMAP Design 17 folds both into ``train.py``.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import optax

from benchmarks import harness
from benchmarks.drivers import train


MIN_LEAF = 128  # values; a smaller leaf (the 16 of a B or C norm's scale) is too few to average the rounding


@jax.jit
def moment_errors(got: dict, want: dict) -> dict:
    """``{leaf's path: |got - want| / |want|}``, 2-norms in float32, of the leaves
    that hold ``MIN_LEAF`` values or more: over nine seeds on the chip the two
    16-value leaves read 0.017 to 0.092 where each other leaf stayed within a
    fifth of its median (PERF.md §4); what they scale reaches the wider leaves."""
    def error(g, w):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        return jnp.linalg.norm((g - w).ravel()) / jnp.linalg.norm(w.ravel())

    return {jax.tree_util.keystr(path): error(g, w) for (path, w), g
            in zip(jax.tree_util.tree_flatten_with_path(want)[0], jax.tree.leaves(got)) if w.size >= MIN_LEAF}


def first_moment(optimizer, grads: dict, params: dict) -> dict:
    """The first moment ``optimizer`` holds after one update by ``grads`` from a
    fresh state."""
    def one_update(g, p):
        return optax.tree_utils.tree_get(optimizer.update(g, optimizer.init(p), p)[1], "mu")

    return jax.jit(one_update)(grads, params)


def judge(check: dict, errors: dict) -> tuple[bool, dict]:
    """Whether every leaf is within the limit, and the note that says why."""
    errors = {path: float(e) for path, e in errors.items()}
    worst = max(errors, key=lambda path: (errors[path] != errors[path], errors[path]))  # a NaN first
    ok = all(e <= check["first_moment_tolerance"] for e in errors.values())
    return ok, {"checks": {"first_moment": ok}, "first_moment_error": errors[worst],
                "worst_leaf": worst, "limit": check["first_moment_tolerance"], "errors": errors}


class FirstStepWatch:
    """The cell's step, unchanged but for its first call, which also reads the
    first moment it leaves against ``reference(params, x, y)``. Nothing is
    fetched: ``errors`` holds device scalars until the run is over."""

    def __init__(self, step, reference):
        self.step, self.reference, self.lower, self.errors = step, reference, step.lower, None

    def __call__(self, params, opt_state, x, y):
        if self.errors is not None:
            return self.step(params, opt_state, x, y)
        want = self.reference(params, x, y)
        out = self.step(params, opt_state, x, y)
        moment = optax.tree_utils.tree_get(out[1], "mu")["layers"]
        self.errors = moment_errors({i: moment[i] for i in want}, want)
        return out


def reference_moment(family, config: dict, optimizer, rehearse: bool, precision: str = "float32"):
    """``(params, x, y) -> {i: first moment tree}`` of the family's reference
    gradient for its watched layers, under ``optimizer``."""
    def moment(params, x, y):
        grads = family.reference_layer_grads(config, params, x, y, rehearse, precision)
        watched = {i: jax.tree.map(lambda a: a.astype(jnp.float32), params["layers"][i]) for i in grads}
        return first_moment(optimizer, grads, watched)

    return moment


def run(r: harness.Run) -> dict:
    family = importlib.import_module(f"benchmarks.families.{r.config['family']}")
    build_step, watches = train.build_step, []

    def build_watched(*args, **kwargs):
        *rest, optimizer, step = build_step(*args, **kwargs)
        watches.append(FirstStepWatch(step, reference_moment(family, r.config, optimizer, r.rehearse)))
        return (*rest, optimizer, watches[-1])

    train.build_step = build_watched
    try:
        out = train.run(r)
    finally:
        train.build_step = build_step
    ok, note = judge(r.traffic["check"], watches[-1].errors)
    harness.note(phase="check_first_step", **note)
    out["correct"] = out["correct"] and ok

    shape, notes = family.shape(r.config, r.rehearse), out["notes"]
    tokens = notes["tokens_per_step"]
    seq = min(r.traffic["seq"], shape["max_seq"]) if r.rehearse else r.traffic["seq"]
    notes["flops_per_step"] = family.train_flops(shape, tokens, seq)
    notes["attention_flops_per_step"] = family.attention_train_flops(shape, tokens, seq)
    notes["attention_bytes_per_step"] = family.attention_train_bytes(shape, tokens)
    notes["scan_bytes_per_step"] = family.scan_train_bytes(shape, tokens)
    return out
