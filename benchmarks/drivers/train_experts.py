"""The loop for cells of kind ``train_experts``: ``drivers/train.py``'s loop
with what a family of sparse experts brings, wrapped the way
``drivers/train_counted.py`` wraps it (whose ``run`` asks every family for
``scan_train_bytes``).

Its counts: ``train_flops``, ``attention_train_flops``, ``attention_train_bytes``,
``expert_train_flops`` and ``expert_train_bytes`` of the family, not
``benchmarks/flops.py``'s dense GPT-2 block.

Its check of the backward is ``train_counted``'s (``first_moment`` is that
file's): adam's first moment after the step's first call against the same
optimizer's moment of the plain reference's float32 gradient, leaf by leaf over
the family's watched layers. Two things differ. Of a watched layer's three
expert leaves the comparison holds a few experts (the family's choice:
``reference_layer_grads`` / ``watched_view``), not all: 64 experts are 1.6 GB in
float32 a layer and a side, and the reference's moments stay on the chip while
the step's first call runs beside 10.7 GB of state. And an expert's matrices
are judged by the MEDIAN over the watched experts (:func:`judge`): the program
rounds activations to bfloat16 and the reference does not, so a token whose
8th and 9th router probabilities nearly tie takes another expert on one side
than on the other, and at random weights every occurrence of a frequent token
ties alike; one such token moves two experts' gradients by 0.1 and more (PERF.md
§4) and says nothing about the kernels, which treat every expert the same.

Its counter: after the window, ``moe_load_max`` (``layer_metrics/moe_load_max.py``)
from the program's ``expert_load`` on the ``loss_step`` batch under the weights the
run ended with; the pairs each expert got are noted beside it in every run.
"""

from __future__ import annotations

import importlib
import statistics

import jax
import jax.numpy as jnp
import optax

from benchmarks import harness
from benchmarks.drivers import train, train_counted


MIN_LEAF = train_counted.MIN_LEAF


@jax.jit
def moment_errors(got: dict, want: dict) -> dict:
    """``train_counted.moment_errors`` with one case more: where the reference's
    leaf is all zeros (an expert no token of the first batch was routed to) the
    error is 0 if the program's is zeros too and infinite if not, where the
    quotient would read NaN; a NaN in the program's moment still reads NaN."""
    def error(g, w):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        off, size = jnp.linalg.norm((g - w).ravel()), jnp.linalg.norm(w.ravel())
        return jnp.where(size > 0, off / size, jnp.where(off > 0, jnp.inf, off))

    return {jax.tree_util.keystr(path): error(g, w) for (path, w), g
            in zip(jax.tree_util.tree_flatten_with_path(want)[0], jax.tree.leaves(got)) if w.size >= MIN_LEAF}


class FirstStepWatch:
    """``train_counted.FirstStepWatch`` over ``view(layer's moment, layer's
    reference moment)`` of each watched layer, and with a hold on the weights
    the last call returned."""

    def __init__(self, step, reference, view):
        self.step, self.reference, self.view, self.lower = step, reference, view, step.lower
        self.errors = self.params = None

    def __call__(self, params, opt_state, x, y):
        if self.errors is not None:
            out = self.step(params, opt_state, x, y)
        else:
            want = self.reference(params, x, y)
            out = self.step(params, opt_state, x, y)
            moment = optax.tree_utils.tree_get(out[1], "mu")["layers"]
            self.errors = moment_errors({i: self.view(moment[i], want[i]) for i in want}, want)
        self.params = out[0]
        return out


def judge(check: dict, errors: dict) -> tuple[bool, dict]:
    """Whether the first moment is within ``check["first_moment_tolerance"]``,
    and the note that says why: every leaf but the experts' matrices by
    itself, the experts' matrices by the median over the watched experts of each
    (layer, matrix). A NaN anywhere is not correct."""
    errors = {path: float(e) for path, e in errors.items()}
    judged, by_matrix = {}, {}
    for path, e in errors.items():
        if "['experts']" in path:
            layer, matrix = path.split("['moe']")[0], path.rsplit("[", 1)[1]
            by_matrix.setdefault(f"{layer}['moe']['experts'][median][{matrix}", []).append(e)
        else:
            judged[path] = e
    judged.update({path: float("nan") if any(e != e for e in es) else statistics.median(es)
                   for path, es in by_matrix.items()})
    worst = max(judged, key=lambda path: (judged[path] != judged[path], judged[path]))  # a NaN first
    ok = all(e <= check["first_moment_tolerance"] for e in judged.values())
    return ok, {"checks": {"first_moment": ok}, "first_moment_error": judged[worst], "worst_leaf": worst,
                "limit": check["first_moment_tolerance"], "largest_single_expert": max(
                    (e for path, e in errors.items() if "['experts']" in path), default=None),
                "judged": judged, "errors": errors}


def reference_moment(family, config: dict, optimizer, rehearse: bool, precision: str = "float32", like=None):
    """``(params, x, y) -> {i: first moment tree}`` of the family's reference
    gradient for its watched layers and experts (those of ``like``, such a
    tree, where a control is held to the reference's own choice), under
    ``optimizer``."""
    def moment(params, x, y):
        experts = like and {i: tuple(tree["moe"]["experts"]) for i, tree in like.items()}
        grads = family.reference_layer_grads(config, params, x, y, rehearse, precision, experts)
        watched = {i: jax.tree.map(lambda a: a.astype(jnp.float32),
                                   family.watched_view(params["layers"][i], grads[i])) for i in grads}
        return train_counted.first_moment(optimizer, grads, watched)

    return moment


def run(r: harness.Run) -> dict:
    family = importlib.import_module(f"benchmarks.families.{r.config['family']}")
    build_step, built = train.build_step, []

    def build_watched(*args, **kwargs):
        _, model, mesh, optimizer, step = build_step(*args, **kwargs)
        watch = FirstStepWatch(step, reference_moment(family, r.config, optimizer, r.rehearse),
                               family.watched_view)
        built.append((model, watch))
        return family, model, mesh, optimizer, watch

    train.build_step = build_watched
    try:
        out = train.run(r)
    finally:
        train.build_step = build_step
    model, watch = built[-1]
    ok, note = judge(r.traffic["check"], watch.errors)
    harness.note(phase="check_first_step", **note)
    out["correct"] = out["correct"] and ok

    shape, notes = family.shape(r.config, r.rehearse), out["notes"]
    tokens = notes["tokens_per_step"]
    seq = min(r.traffic["seq"], shape["max_seq"]) if r.rehearse else r.traffic["seq"]
    notes["flops_per_step"] = family.train_flops(shape, tokens, seq)
    notes["attention_flops_per_step"] = family.attention_train_flops(shape, tokens, seq)
    notes["attention_bytes_per_step"] = family.attention_train_bytes(shape, tokens)
    notes["expert_flops_per_step"] = family.expert_train_flops(shape, tokens)
    notes["expert_bytes_per_step"] = family.expert_train_bytes(shape, tokens)

    # outside the window and the trace: the pairs each expert of layer 0 gets on the loss step's batch
    generator = importlib.import_module(f"benchmarks.traffic.{r.traffic['data']['generator']}").Generator(
        r.traffic["data"], r.seed, shape["vocab_size"], tokens // seq, seq)
    pairs = jax.jit(model.expert_load)(watch.params, generator.batch(r.traffic["loss_step"])[0]).tolist()
    notes["moe_load_max"] = max(pairs) * len(pairs) / sum(pairs)
    harness.note(phase="routing", layer=0, batch_of_step=r.traffic["loss_step"], after_steps=out["attempted"],
                 pairs_per_expert=pairs, mean_pairs=sum(pairs) / len(pairs), moe_load_max=notes["moe_load_max"])
    return out
