"""The readings ``ouro-8k``'s two limits are set between, on the chip at the
cell's sizes, each judged by the cell's own comparison
(``drivers/train_looped.judge`` and the traffic file's ``check``): the program
as it is has to come out correct, every deliberate fault not.

    python3 scripts/ouro_tolerance_check.py [--seeds N ...] [--fault-seeds N ...]    # on a TPU v5e

Weights and the first batch are the cell's own for each seed; the step is the
cell's (``drivers/train.build_step``), called once on fresh weights. Against
the float32 reference on the same weights, per seed: the step-1 loss (nats) and
the first moment's largest error over the watched leaves (the first and the
last layer, the exit gate, the final norm) of

- ``program``: the step as it is;
- ``matmuls_float8``, on every seed, and each other of
  ``benchmarks/reference/ouro.py``'s ``VARIANTS`` but ``float32`` on the
  ``--fault-seeds``: the reference itself computed with a deliberate fault,
  standing where the program stands (three passes, the gate left out, the last
  exit alone, the entropy's sign flipped, the sandwich norms left out, the
  unnormed state carried, the rotation by pairs, the matmuls on float8
  operands).

Beside the gate's bias's error (in units of its terms' magnitudes,
``drivers/train_looped.py``) each line gives ``bias_cancellation``,
``|Σ terms| / Σ |terms|`` of the float32 reference, and ``bias_relative``,
the error relative to the bias's own gradient. Each line is one JSON object;
the last one holds every reading.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="ouro-8k")
    ap.add_argument("--seeds", type=int, nargs="+", default=[4300000101])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[],
                    help="seeds (of --seeds) on which every deliberate fault is read, not float8 alone")
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes on the CPU")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import harness
    from benchmarks.drivers import train, train_looped
    from benchmarks.reference import ouro as reference
    from dsml_tpu.parallel.hybrid import init_hybrid

    _, cell, config, traffic = harness.resolve(args.workload)
    harness.configure_compile_cache()
    family, model, mesh, optimizer, step = train.build_step(
        config, traffic, jax.devices()[:traffic["chips"]], args.rehearse)
    shape, check = family.shape(config, args.rehearse), traffic["check"]
    rows, seq = (2, shape["max_seq"]) if args.rehearse else (traffic["rows_per_chip"], traffic["seq"])
    print(json.dumps({"cell": cell["name"], "device": jax.devices()[0].device_kind, "rows": rows, "seq": seq,
                      "check": {k: v for k, v in check.items() if k.endswith(("tolerance", "nats"))}}), flush=True)
    out = {}

    for seed in args.seeds:
        x, y = importlib.import_module(f"benchmarks.traffic.{traffic['data']['generator']}").Generator(
            traffic["data"], seed, shape["vocab_size"], rows, seq).batch(1)
        params, opt_state = init_hybrid(model, optimizer, mesh, seed=seed)
        exact_loss = family.reference_loss(config, params, x, y, args.rehearse)
        exact = train_looped.reference_moment(family, config, optimizer, args.rehearse)(params, x, y)

        bias_grad = abs(float(exact["exit_gate"]["b"][0]))
        cancellation = bias_grad / float(exact[train_looped.TERMS])

        def judged(name, loss, errors):
            ok, note = train_looped.judge(check, errors)
            by_loss = abs(loss - exact_loss) <= check["reference_tolerance_nats"]
            bias = note["gate_bias"]["error"]
            line = {"seed": seed, "variant": name, "correct": ok and by_loss, "loss_diff": loss - exact_loss,
                    "checks": {"reference": by_loss, **note["checks"]},
                    "first_moment_error": note["first_moment_error"], "worst_leaf": note["worst_leaf"],
                    "gate_bias": bias, "bias_cancellation": cancellation, "bias_relative": bias / cancellation,
                    "errors": note["errors"]}
            print(json.dumps(line), flush=True)
            out[f"{seed}/{name}"] = line

        faults = reference.VARIANTS[1:] if seed in args.fault_seeds else ("matmuls_float8",)
        for variant in faults:
            control = train_looped.reference_moment(family, config, optimizer, args.rehearse, variant)
            loss = reference.loss(params, x, y, s=family.reference_sizes(shape), variant=variant)
            judged(variant, loss, train_looped.moment_errors(control(params, x, y), exact))

        watch = train_looped.FirstStepWatch(step, lambda *_: exact, family.watched_view)
        _, _, loss = watch(params, opt_state, x, y)
        judged("program", float(loss), watch.errors)
        del params, opt_state, exact

    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
