"""The readings the limits of a cell of kind ``train_experts`` or
``train_leaf_limits`` (``mellum2-8k``, ``kanana2-8k``) are set between, on the
chip at the cell's sizes, each judged by the cell's own comparison (the
``judge`` of the cell's driver over ``drivers/train_experts``'s watched leaves,
and the traffic file's ``check``): the program as it is has to come out
correct, every control not.

    python3 scripts/mellum_tolerance_check.py [--workload kanana2-8k] [--seed N] [--variants ...] [--more-seeds N ...]

Weights and the first batch are the cell's own for ``--seed``; the step is the
cell's (``drivers/train.build_step``), called once on fresh weights. Per variant,
against the float32 reference on the same weights: the step-1 loss (nats) and the
first moment's largest error over the watched leaves.

- ``program``: the step as it is.
- every other name is one of the family's reference's deliberate faults
  (``VARIANTS`` of ``benchmarks/reference/mellum.py`` or ``deepseek_v3.py``), the
  reference itself standing where the program stands.

The last line is one JSON object.
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
    ap.add_argument("--workload", default="mellum2-8k")
    ap.add_argument("--seed", type=int, default=3500000101)
    ap.add_argument("--variants", nargs="*", help="the faults to run (default: all)")
    ap.add_argument("--more-seeds", type=int, nargs="*", default=[], help="further seeds, the program alone")
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes on the CPU")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import harness
    from benchmarks.drivers import train, train_experts
    from dsml_tpu.parallel.hybrid import init_hybrid

    _, cell, config, traffic = harness.resolve(args.workload)
    driver = importlib.import_module(f"benchmarks.drivers.{traffic['kind']}")
    harness.configure_compile_cache()
    family, model, mesh, optimizer, step = train.build_step(
        config, traffic, jax.devices()[:traffic["chips"]], args.rehearse)
    shape, check, reference = family.shape(config, args.rehearse), traffic["check"], family.reference
    rows, seq = (2, shape["max_seq"]) if args.rehearse else (traffic["rows_per_chip"], traffic["seq"])
    generator = importlib.import_module(f"benchmarks.traffic.{traffic['data']['generator']}").Generator
    print(json.dumps({"cell": cell["name"], "device": jax.devices()[0].device_kind, "rows": rows, "seq": seq,
                      "check": {k: v for k, v in check.items() if k.endswith(("tolerance", "nats"))}}), flush=True)
    sizes, out = family.reference_sizes(shape), {}

    def one_seed(seed: int, variants) -> None:
        x, y = generator(traffic["data"], seed, shape["vocab_size"], rows, seq).batch(1)
        params, opt_state = init_hybrid(model, optimizer, mesh, seed=seed)
        exact_loss = reference.loss(params, x, y, s=sizes)
        exact = train_experts.reference_moment(family, config, optimizer, args.rehearse)(params, x, y)

        def judged(name, loss, errors):
            ok, note = driver.judge(check, errors)
            by_loss = abs(loss - exact_loss) <= check["reference_tolerance_nats"]
            line = {"seed": seed, "variant": name, "correct": ok and by_loss, "loss_diff": loss - exact_loss,
                    "checks": {"reference": by_loss, **note["checks"]},
                    "first_moment_error": note["first_moment_error"], "worst_leaf": note["worst_leaf"],
                    "largest_single_expert": note["largest_single_expert"], "judged": note["judged"],
                    **({"leaf_limits": note["leaf_limits"]} if "leaf_limits" in note else {})}
            print(json.dumps(line), flush=True)
            out[f"{name}@{seed}"] = line

        expert_layers = {i: tree for i, tree in exact.items() if "moe" in tree}  # a control's experts are the exact's
        for variant in variants:
            control = train_experts.reference_moment(family, config, optimizer, args.rehearse, variant,
                                                     like=expert_layers)
            judged(variant, reference.loss(params, x, y, s=sizes, variant=variant),
                   train_experts.moment_errors(control(params, x, y), exact))
        watch = train_experts.FirstStepWatch(step, lambda *_: exact, family.watched_view)
        _, _, loss = watch(params, opt_state, x, y)
        judged("program", float(loss), watch.errors)

    one_seed(args.seed, args.variants if args.variants is not None else reference.VARIANTS[1:])
    for seed in args.more_seeds:
        one_seed(seed, ())

    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
