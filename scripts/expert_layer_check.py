"""The expert layer alone on the chip, at a cell's shape: ``jax.value_and_grad``
of ``models/experts.py::expert_layer``, bf16, under the checkpoint a block of
``models/stack.py`` runs it in (all but what ``PLAN_NAMES`` tags computed again in
the backward); device time a call split by the layer's own scopes into route
(router, top-k, sort, plan) / dispatch (gather) / experts (the grouped matmuls, and
of them the kernels by name) / combine, forward, recomputed forward and backward
together. ``--workload mellum2-8k``: ``[8192, 2304]`` tokens, 64 gated experts of
width 896, 8 a token, softmax router, all held. ``--workload kanana2-8k``:
``[8192, 2048]`` tokens, the sigmoid router with its selection bias over 128
experts, 6 a token, experts 0..63 of width 768 held.

    python3 scripts/expert_layer_check.py [--workload kanana2-8k] [--tiles 128 256 512]   # on the chip

Each ``--tiles`` entry (the row tile of the grouped matmuls) is timed under each
routing: ``uniform``, the router drawn like the model's; ``collapsed``, a router of
zeros, which sends every token, the same rows, to the same ``top_k`` experts (all
held); and where a share is held, ``all_held`` and ``none_held``, a selection bias
that puts every pair on the held experts or none on them (the router drawn, so the
pairs spread over the experts chosen). The work is a function of shapes alone
(``ops/grouped_matmul.py``), so all must take the same time; ``ratio`` is each over
uniform (``collapsed_ratio`` the first of them), and ``gmm_fwd_calls`` the forward
kernel's calls in the differentiated jaxpr (5: three forward, the gate's two again). Values are held to
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


# (tokens, d_model, expert width, router outputs, top_k, experts held, routed scaling or None: softmax)
SHAPES = {"mellum2-8k": (8192, 2304, 896, 64, 8, 64, None), "kanana2-8k": (8192, 2048, 768, 128, 6, 64, 2.448)}
REHEARSE = {"mellum2-8k": (256, 128, 128, 8, 2, 8, None), "kanana2-8k": (256, 128, 128, 8, 2, 4, 2.448)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", choices=sorted(SHAPES), default="mellum2-8k")
    ap.add_argument("--tiles", type=int, nargs="*", default=[256])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true", help="tiny shapes, for a run without the chip")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks import moe_reduce, trace_reduce
    from dsml_tpu.models.experts import PLAN_NAMES, expert_layer, route

    tokens, d, f, experts, top_k, held, scaling = (REHEARSE if args.rehearse else SHAPES)[args.workload]
    tiles = [16] if args.rehearse else args.tiles
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform, "workload": args.workload,
                      "shape": [tokens, d, f, experts, top_k, held]}), flush=True)

    ks = jax.random.split(jax.random.key(args.seed), 7)

    def normal(key, *shape, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)

    p = {"router": normal(ks[0], d, experts), "w_gate": normal(ks[1], held, d, f),
         "w_up": normal(ks[2], held, d, f), "w_down": normal(ks[3], held, f, d)}
    x = jax.random.normal(ks[4], (tokens, d)).astype(jnp.bfloat16)
    weight = jax.random.normal(ks[5], (tokens, d))
    # equal logits: every token takes the first top_k experts (ties go to the lower index), on the same rows
    routings = {"uniform": p, "collapsed": {**p, "router": jnp.zeros_like(p["router"])}}
    if scaling is not None:  # the sigmoid router: a selection bias beside it
        bias = normal(ks[6], experts, std=0.01)
        on_held = jnp.arange(experts) < held  # sigmoid scores lie in (0, 1): a bias of 1 decides the choice
        routings = {"uniform": {**p, "bias": bias}, "collapsed": {**routings["collapsed"], "bias": jnp.zeros_like(bias)},
                    "all_held": {**p, "bias": jnp.where(on_held, 1.0, 0.0).astype(bias.dtype)},
                    "none_held": {**p, "bias": jnp.where(on_held, 0.0, 1.0).astype(bias.dtype)}}

    out = {}
    for tile in tiles:
        layer = jax.checkpoint(lambda p, x: expert_layer(p, x, top_k=top_k, tile=tile, experts_held=(0, held),
                                                         routed_scaling=scaling or 1.0),
                               policy=jax.checkpoint_policies.save_only_these_names(*PLAN_NAMES))
        fn = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32) * weight), argnums=(0, 1)))
        row = {"gmm_fwd_calls": kernel_calls(jax.make_jaxpr(fn)(routings["uniform"], x).jaxpr, "gmm_fwd")}
        for name, params in routings.items():
            operands = (params, x)
            top_e, _ = route(x, params["router"], top_k, params.get("bias"), scaling or 1.0)
            load = jnp.sum(top_e.reshape(-1, 1) == jnp.arange(experts), axis=0)
            for _ in range(2):
                jax.block_until_ready(fn(*operands))
            where = TRACE_DIR / f"{tile}-{name}"
            shutil.rmtree(where, ignore_errors=True)
            with jax.profiler.trace(str(where)):
                for _ in range(args.calls):
                    jax.block_until_ready(fn(*operands))
            ops = next(iter(trace_reduce.load(str(where))["devices"].values()), None)
            row[name] = {"largest_expert_rows": int(load.max()), "experts_with_rows": int((load > 0).sum()),
                         "pairs_on_held": int(load[:held].sum())}
            if ops:
                table = moe_reduce.read_dir(str(where), args.calls)
                busy = trace_reduce.length(trace_reduce.union([[e[2], e[2] + e[3]] for e in ops])) / 1e6 / args.calls
                row[name].update({"layer_ms": busy, "gmm_kernels_ms": sum(table.get(k, 0.0) for k in moe_reduce.KERNELS),
                                  **{k: table.get(k, 0.0) for k in (*moe_reduce.SCOPES, *moe_reduce.KERNELS)}})
        if "layer_ms" in row["uniform"]:
            row["ratio"] = {name: row[name]["layer_ms"] / row["uniform"]["layer_ms"] for name in routings if name != "uniform"}
            row["collapsed_ratio"] = row["ratio"]["collapsed"]
        out[f"tile_{tile}"] = row
        print(json.dumps({f"tile_{tile}": row}), flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
