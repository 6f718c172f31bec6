"""The loop for cells of kind ``train_looped``: ``drivers/train.py``'s loop,
wrapped as ``drivers/train_counted.py`` wraps it, for a family whose layers
run several times a step with the same weights and whose loss is taken at an
exit after every pass (``families/ouro.py``).

Its counts: the family's ``train_flops``, ``attention_train_flops`` and
``attention_train_bytes``, every pass and every exit.

Its check of the backward: ``train_counted``'s comparison of adam's first
moment after the step's first call with the same optimizer's moment of the
plain reference's float32 gradient, ``|program - reference| / |reference|`` in
the 2-norm, leaf by leaf, against ``check.first_moment_tolerance``. What is
compared is the family's ``watched_view`` of the parameters, not layers alone:
the watched layers (each leaf's gradient the sum of its uses in every pass),
the exit gate's weight and the final norm, which the recurrence and the exits
run through. The gate's bias is one value, a sum over every token and exit
that cancels to near zero on some seeds, so its error relative to itself has
no scale: it is held to a limit of its own, ``check.gate_bias_tolerance``, on
``|program - reference|`` over the moment of ``Σ_t,i |∂loss / ∂z_t,i|``, the
magnitudes of its terms (``z_t,i`` the gate's logit of token ``i`` at exit
``t``), which the reference gives as ``exit_gate_terms`` (PERF.md §4).
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import optax

from benchmarks import harness
from benchmarks.drivers import train, train_counted

BIAS, TERMS = "['exit_gate']['b']", "exit_gate_terms"


@jax.jit
def moment_errors(got: dict, want: dict) -> dict:
    """``train_counted.moment_errors`` of every leaf but the bias's terms (the
    bias, one value, is under ``train_counted.MIN_LEAF`` and left out there),
    and the gate's bias under ``BIAS``: ``|got - want|`` over ``want``'s moment
    of the terms. A state left unchanged reads ``|Σ terms| / Σ |terms|``."""
    terms = want[TERMS]
    got, want = ({k: v for k, v in tree.items() if k != TERMS} for tree in (got, want))
    bias = jnp.abs(got["exit_gate"]["b"] - want["exit_gate"]["b"]).sum() / terms
    return {**train_counted.moment_errors(got, want), BIAS: bias}


def judge(check: dict, errors: dict) -> tuple[bool, dict]:
    """``train_counted.judge`` over every leaf but the gate's bias, and the bias
    within ``check.gate_bias_tolerance`` (a NaN is not correct)."""
    rest = {path: e for path, e in errors.items() if path != BIAS}
    ok, note = train_counted.judge(check, rest)
    bias = float(errors[BIAS])
    by_bias = bias <= check["gate_bias_tolerance"]
    note["checks"]["gate_bias"] = by_bias
    note["gate_bias"] = {"error": bias, "limit": check["gate_bias_tolerance"]}
    return ok and by_bias, note


class FirstStepWatch:
    """The cell's step, unchanged but for its first call, which also reads the
    first moment it leaves, cut by ``view``, against ``reference(params, x, y)``.
    Nothing is fetched: ``errors`` holds device scalars until the run is over."""

    def __init__(self, step, reference, view):
        self.step, self.reference, self.view, self.lower, self.errors = step, reference, view, step.lower, None

    def __call__(self, params, opt_state, x, y):
        if self.errors is not None:
            return self.step(params, opt_state, x, y)
        want = self.reference(params, x, y)
        out = self.step(params, opt_state, x, y)
        self.errors = moment_errors(self.view(optax.tree_utils.tree_get(out[1], "mu")), want)
        return out


def reference_moment(family, config: dict, optimizer, rehearse: bool, variant: str = "float32"):
    """``(params, x, y) ->`` the first moment, shaped as ``family.watched_view``
    with the bias's terms beside it (``TERMS``), of the family's reference
    gradient under ``optimizer``."""
    def moment(params, x, y):
        grads = family.reference_grads(config, params, x, y, rehearse, variant)
        watched = jax.tree.map(lambda a: a.astype(jnp.float32), family.watched_view(params))
        return train_counted.first_moment(optimizer, grads, {**watched, TERMS: jnp.zeros((), jnp.float32)})

    return moment


def run(r: harness.Run) -> dict:
    family = importlib.import_module(f"benchmarks.families.{r.config['family']}")
    build_step, watches = train.build_step, []

    def build_watched(*args, **kwargs):
        *rest, optimizer, step = build_step(*args, **kwargs)
        watches.append(FirstStepWatch(step, reference_moment(family, r.config, optimizer, r.rehearse),
                                      family.watched_view))
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
    return out
