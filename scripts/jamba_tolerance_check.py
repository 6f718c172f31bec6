"""The readings a cell's two limits are set between, on the chip at the cell's
sizes, each judged by the cell's own comparison (``drivers/train_counted.judge``
and the traffic file's ``check``): the program as it is has to come out correct,
every control not.

    chiprun -- python3 scripts/jamba_tolerance_check.py [--workload jamba2-3b-8k] [--seed N]

Weights and the first batch are the cell's own for ``--seed``; the step is the
cell's (``drivers/train.build_step``), called once on fresh weights a variant.
Per variant, against the float32 reference on the intact weights: the step-1
loss (nats) and the first moment's largest error over the watched leaves.

- ``program``: the step as it is; also, for the record and under no limit, the
  same error of the parameters' change over the step (PERF.md §4 says why the
  limit is not on it).
- ``program_without_<leaf>``: ``D``, ``b_dt`` or the ``dt`` norm's scale zeroed in
  every Mamba layer of the program alone: a dropped term.
- ``reference_in_<precision>``: the reference itself with its scan in bfloat16,
  float16 and float8_e4m3fn, standing where the program stands.

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
    ap.add_argument("--workload", default="jamba2-3b-8k")
    ap.add_argument("--seed", type=int, default=2700000101)
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes on the CPU")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks import harness
    from benchmarks.drivers import train, train_counted
    from benchmarks.reference import jamba as reference
    from dsml_tpu.parallel.hybrid import init_hybrid

    _, cell, config, traffic = harness.resolve(args.workload)
    harness.configure_compile_cache()
    family, model, mesh, optimizer, step = train.build_step(
        config, traffic, jax.devices()[:traffic["chips"]], args.rehearse)
    shape, check = family.shape(config, args.rehearse), traffic["check"]
    rows, seq = (2, shape["max_seq"]) if args.rehearse else (traffic["rows_per_chip"], traffic["seq"])
    x, y = importlib.import_module(f"benchmarks.traffic.{traffic['data']['generator']}").Generator(
        traffic["data"], args.seed, shape["vocab_size"], rows, seq).batch(1)
    print(json.dumps({"cell": cell["name"], "device": jax.devices()[0].device_kind, "rows": rows, "seq": seq,
                      "check": {k: v for k, v in check.items() if k.endswith(("tolerance", "nats"))}}), flush=True)

    def fresh():
        return init_hybrid(model, optimizer, mesh, seed=args.seed)

    def zeroed(leaf):
        return jax.device_put(jnp.zeros_like(leaf), leaf.sharding)

    def judged(name, loss, errors, **more):
        ok, note = train_counted.judge(check, errors)
        by_loss = abs(loss - exact_loss) <= check["reference_tolerance_nats"]
        line = {"variant": name, "correct": ok and by_loss, "loss_diff": loss - exact_loss,
                "checks": {"reference": by_loss, **note["checks"]},
                "first_moment_error": note["first_moment_error"], "worst_leaf": note["worst_leaf"], **more}
        print(json.dumps(line), flush=True)
        out[name] = line

    def reference_loss(params, precision="float32"):
        return reference.loss(params, x, y, n_head=shape["n_head"], n_kv_head=shape["n_kv_head"],
                              eps=shape["rms_eps"], precision=precision)

    params, opt_state = fresh()
    exact_loss = reference_loss(params)
    exact = train_counted.reference_moment(family, config, optimizer, args.rehearse)(params, x, y)
    out = {"seed": args.seed, "reference_loss": exact_loss}

    for precision in ("bfloat16", "float16", "float8_e4m3fn"):
        control = train_counted.reference_moment(family, config, optimizer, args.rehearse, precision)
        judged(f"reference_in_{precision}", reference_loss(params, precision),
               train_counted.moment_errors(control(params, x, y), exact))

    # the parameters' change over the step, for the record: the reference's is the same
    # optimizer's update of its float32 gradient
    watched = {i: jax.tree.map(lambda a: a.astype(jnp.float32), params["layers"][i]) for i in exact}
    grads = family.reference_layer_grads(config, params, x, y, args.rehearse)
    change = jax.jit(lambda g, p: optimizer.update(g, optimizer.init(p), p)[0])(grads, watched)
    del grads

    for leaf in (None, "d", "b_dt", "dt_norm"):
        if leaf:
            params, opt_state = fresh()
            params["layers"] = [{**layer, "ssm": {**layer["ssm"], leaf: zeroed(layer["ssm"][leaf])}}
                                if "ssm" in layer else layer for layer in params["layers"]]
        watch = train_counted.FirstStepWatch(step, lambda *_: exact)
        params, opt_state, loss = watch(params, opt_state, x, y)
        more = {}
        if not leaf:
            moved = {i: jax.tree.map(lambda new, old: new.astype(jnp.float32) - old, params["layers"][i], watched[i])
                     for i in watched}
            errors = {k: float(v) for k, v in train_counted.moment_errors(moved, change).items()}
            more = {"parameter_change_error": errors}
            del moved, change, watched
        judged(f"program_without_{leaf}" if leaf else "program", float(loss), watch.errors, **more)
        del params, opt_state

    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
