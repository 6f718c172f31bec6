"""The expert layer alone on the chip, at the cell's shape: ``jax.value_and_grad``
of ``models/experts.py::expert_layer`` on ``[8192, 2304]`` tokens, 64 gated experts
of width 896, 8 a token, bf16, under the checkpoint a block of ``models/mellum.py``
runs it in (all but what ``PLAN_NAMES`` tags computed again in the backward); device
time a call split by the layer's own scopes into route (router, top-k, sort, plan)
/ dispatch (gather) / experts (the grouped matmuls, and of them the kernels by
name) / combine, forward, recomputed forward and backward together.

    chiprun -- python3 scripts/expert_layer_check.py [--tiles 128 256 512]

Each ``--tiles`` entry (the row tile of the grouped matmuls) is timed twice: with
the router drawn like the model's (near-uniform routing) and with a router of
zeros, which sends every token, the same rows, to the same 8 experts. The work is a function of shapes alone
(``ops/grouped_matmul.py``), so the two must take the same time; ``collapsed_ratio``
is collapsed over uniform, and ``gmm_fwd_calls`` the forward kernel's calls in the
differentiated jaxpr (5: three forward, the gate's two again). Values are held to
the dense form (every expert for every token) at ``--rehearse`` sizes on the CPU
and by ``tests/test_mellum.py``; on the chip the cell's own check does that at the
published widths. Time is the device's, from a ``jax.profiler`` trace of ``--calls``
calls after two warm ones, reduced by ``benchmarks/moe_reduce``. The last line is
one JSON object.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
TRACE_DIR = REPO / ".bench_trace" / "expert_layer"


def kernel_calls(jaxpr, name: str) -> int:
    """The ``pallas_call``s named ``name`` in ``jaxpr`` and the jaxprs its equations hold."""
    import jax

    return sum((eqn.primitive.name == "pallas_call" and eqn.params["name"] == name)
               + sum(kernel_calls(inner, name) for inner in jax.core.jaxprs_in_params(eqn.params)) for eqn in jaxpr.eqns)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiles", type=int, nargs="*", default=[256])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true", help="tiny shapes, for a run without the chip")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks import moe_reduce, trace_reduce
    from dsml_tpu.models.experts import PLAN_NAMES, expert_layer, route

    tokens, d, f, experts, top_k = (256, 128, 128, 8, 2) if args.rehearse else (8192, 2304, 896, 64, 8)
    tiles = [16] if args.rehearse else args.tiles
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform,
                      "shape": [tokens, d, f, experts, top_k]}), flush=True)

    ks = jax.random.split(jax.random.key(args.seed), 6)

    def normal(key, *shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(jnp.bfloat16)

    p = {"router": normal(ks[0], d, experts), "w_gate": normal(ks[1], experts, d, f),
         "w_up": normal(ks[2], experts, d, f), "w_down": normal(ks[3], experts, f, d)}
    x = jax.random.normal(ks[4], (tokens, d)).astype(jnp.bfloat16)
    weight = jax.random.normal(ks[5], (tokens, d))
    # equal logits: every token takes the first top_k experts (ties go to the lower index), on the same rows
    collapsed = {**p, "router": jnp.zeros_like(p["router"])}

    out = {}
    for tile in tiles:
        layer = jax.checkpoint(lambda p, x: expert_layer(p, x, top_k=top_k, tile=tile),
                               policy=jax.checkpoint_policies.save_only_these_names(*PLAN_NAMES))
        fn = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32) * weight), argnums=(0, 1)))
        row = {"gmm_fwd_calls": kernel_calls(jax.make_jaxpr(fn)(p, x).jaxpr, "gmm_fwd")}
        for name, operands in (("uniform", (p, x)), ("collapsed", (collapsed, x))):
            top_e, _ = route(operands[1], operands[0]["router"], top_k)
            load = jnp.sum(top_e.reshape(-1, 1) == jnp.arange(experts), axis=0)
            for _ in range(2):
                jax.block_until_ready(fn(*operands))
            where = TRACE_DIR / f"{tile}-{name}"
            shutil.rmtree(where, ignore_errors=True)
            with jax.profiler.trace(str(where)):
                for _ in range(args.calls):
                    jax.block_until_ready(fn(*operands))
            ops = next(iter(trace_reduce.load(str(where))["devices"].values()), None)
            row[name] = {"largest_expert_rows": int(load.max()), "experts_with_rows": int((load > 0).sum())}
            if ops:
                table = moe_reduce.read_dir(str(where), args.calls)
                busy = trace_reduce.length(trace_reduce.union([[e[2], e[2] + e[3]] for e in ops])) / 1e6 / args.calls
                row[name].update({"layer_ms": busy, "gmm_kernels_ms": sum(table.get(k, 0.0) for k in moe_reduce.KERNELS),
                                  **{k: table.get(k, 0.0) for k in (*moe_reduce.SCOPES, *moe_reduce.KERNELS)}})
        if "layer_ms" in row["uniform"]:
            row["collapsed_ratio"] = row["collapsed"]["layer_ms"] / row["uniform"]["layer_ms"]
        out[f"tile_{tile}"] = row
        print(json.dumps({f"tile_{tile}": row}), flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
