"""The program's losses over a cell's first steps beside a second witness that
shares nothing of the program's step: the plain float32 reference
(``benchmarks/reference/ouro.py``, every leaf's gradient, the tables' too)
trained from the same weights, cast up once, on the same batches by the same
optimizer, with its parameters and the optimizer's state in float32. Those
two live on the host (the chip's memory holds the reference's activations and
gradients, not a float32 copy of the model and its moments beside them); each
step takes the parameters to the chip and the gradients back.

    python3 scripts/ouro_steps_witness.py [--seeds N ...] [--steps 8]    # on a TPU v5e
    JAX_PLATFORMS=cpu python3 scripts/ouro_steps_witness.py --rehearse   # tiny sizes on the CPU

The step-1 loss and the first moment say that the program's first step is the
reference's; this says whether the steps after it are: a fault in what is
carried from one step to the next (the optimizer's state of a weight used in
every pass, say) shows as losses that part, while a swing both sides make on
the same batches belongs to the training recipe. One JSON line for the
program's losses, one for each of the witness's steps, as they come.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="ouro-8k")
    ap.add_argument("--seeds", type=int, nargs="+", default=[4300000101])
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes on the CPU")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    import optax

    from benchmarks import harness
    from benchmarks.drivers import train
    from benchmarks.reference import ouro as reference
    from dsml_tpu.parallel.hybrid import init_hybrid

    _, cell, config, traffic = harness.resolve(args.workload)
    harness.configure_compile_cache()
    family, model, mesh, optimizer, step = train.build_step(
        config, traffic, jax.devices()[:traffic["chips"]], args.rehearse)
    shape = family.shape(config, args.rehearse)
    sizes = family.reference_sizes(shape)
    rows, seq = (2, shape["max_seq"]) if args.rehearse else (traffic["rows_per_chip"], traffic["seq"])
    print(json.dumps({"cell": cell["name"], "device": jax.devices()[0].device_kind, "rows": rows, "seq": seq,
                      "steps": args.steps, "step": traffic["step"]}), flush=True)

    host, chip = jax.devices("cpu")[0], jax.devices()[0]

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def update(grads, opt_state, params):  # on the host: its arguments are there
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    for seed in args.seeds:
        generator = importlib.import_module(f"benchmarks.traffic.{traffic['data']['generator']}").Generator(
            traffic["data"], seed, shape["vocab_size"], rows, seq)
        batches = [generator.batch(k) for k in range(1, args.steps + 1)]

        params, opt_state = init_hybrid(model, optimizer, mesh, seed=seed)
        start = jax.device_get(params)
        program = []
        for x, y in batches:
            params, opt_state, loss = step(params, opt_state, x, y)
            program.append(float(loss))
        del params, opt_state
        print(json.dumps({"seed": seed, "program": program}), flush=True)

        params = jax.device_put(jax.tree.map(lambda a: np.asarray(a, np.float32), start), host)
        opt_state = jax.jit(optimizer.init, device=host)(params)
        n_layer = len(params["layers"])
        for k, (x, y) in enumerate(batches, 1):
            on_chip = jax.device_put(params, chip)
            loss = reference.loss(on_chip, x, y, s=sizes)
            g = reference.grads(on_chip, x, y, range(n_layer), s=sizes, tables=True)
            del on_chip
            grads = {"wte": g["wte"], "lm_head": g["lm_head"], "rms_f": g["rms_f"], "exit_gate": g["exit_gate"],
                     "layers": [g["layers"][i] for i in range(n_layer)]}
            params, opt_state = update(jax.device_put(grads, host), opt_state, params)
            del g, grads
            print(json.dumps({"seed": seed, "step": k, "witness": loss, "program": program[k - 1],
                              "diff": program[k - 1] - loss}), flush=True)
        del params, opt_state
    return 0


if __name__ == "__main__":
    sys.exit(main())
