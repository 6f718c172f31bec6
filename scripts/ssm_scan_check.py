"""The selective-scan kernel pair against the plain reference scan, on the chip,
at a cell's sizes: ``y`` and all six gradients, then the kernels' times.

    chiprun -- python3 scripts/ssm_scan_check.py [--seq 8192] [--channels 5120] [--sweep]

The reference is ``benchmarks/reference/jamba.py::selective_scan`` (a sequential
``lax.scan`` in float32 at matmul precision "highest"; its backward keeps the
state at every 256th step and recomputes between).
Inputs are what a Mamba mixer at initialisation feeds the scan: ``u`` of unit
scale, ``delta`` = softplus around steps of 0.001 to 0.1, ``A = -(1..N)``, ``B``
and ``C`` RMS-normalised, ``D = 1``; bf16 in and out as in the cell. Each
difference is printed as a share of the reference's largest magnitude. Times
are host clock around ``block_until_ready`` over ``--repeats`` calls after two
warm ones; the backward's is the gradient call's less the forward's. The last
line is one JSON object; exit 1 where any difference exceeds ``--tolerance``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--channels", type=int, default=5120)
    ap.add_argument("--state", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--tolerance", type=float, default=2e-2,
                    help="largest |kernel - reference| / max|reference| allowed (bf16 outputs: 2^-8 "
                         "a rounding, a few of them through a gradient)")
    ap.add_argument("--sweep", action="store_true", help="also time other block sizes")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks.reference import jamba as reference
    from dsml_tpu.ops.selective_scan import selective_scan

    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform}), flush=True)
    bsz, s, e, n = args.rows, args.seq, args.channels, args.state
    ks = jax.random.split(jax.random.key(args.seed), 7)
    bf16 = jnp.bfloat16

    def unit_rms(x):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6)

    u = jax.random.normal(ks[0], (bsz, s, e)).astype(bf16)
    steps = jnp.exp(jax.random.uniform(ks[1], (e,)) * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    bias = steps + jnp.log(-jnp.expm1(-steps))
    delta = jax.nn.softplus(jax.random.normal(ks[2], (bsz, s, e)) + bias).astype(bf16)
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (e, n))
    b = unit_rms(jax.random.normal(ks[3], (bsz, s, n))).astype(bf16)
    c = unit_rms(jax.random.normal(ks[4], (bsz, s, n))).astype(bf16)
    d = jnp.ones(e, jnp.float32)
    weight = jax.random.normal(ks[5], (bsz, s, e)).astype(bf16)
    operands = (u, delta, a, b, c, d)

    def reference_scan(*ops):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(reference.selective_scan, in_axes=(0, 0, None, 0, 0, None))(*ops)

    def objective(scan):
        return lambda *ops: (scan(*ops).astype(jnp.float32) * weight).sum()

    def grads(scan):
        return jax.jit(jax.grad(objective(scan), argnums=tuple(range(6))))

    out = {"shape": [bsz, s, e, n], "relative_difference": {}}
    y, y_ref = jax.jit(selective_scan)(*operands), jax.jit(reference_scan)(*operands)
    got, want = [y, *grads(selective_scan)(*operands)], [y_ref, *grads(reference_scan)(*operands)]
    for name, g, w in zip(("y", "du", "ddelta", "dA", "dB", "dC", "dD"), got, want):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        out["relative_difference"][name] = float(jnp.abs(g - w).max() / jnp.abs(w).max())
    del got, want, y, y_ref

    def seconds(fn):
        for _ in range(2):
            jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            jax.block_until_ready(fn(*operands))
        return (time.perf_counter() - t0) / args.repeats

    def times(**blocks):
        def scan(*ops):
            return selective_scan(*ops, **blocks)

        fwd = seconds(jax.jit(scan))
        return {"fwd_ms": fwd * 1e3, "bwd_ms": (seconds(grads(scan)) - fwd) * 1e3}

    out["default_blocks"] = times()
    if args.sweep:
        out["sweep"] = {}
        for blocks in ({"block_s": 256}, {"block_e": 256}, {"block_e": 1024}, {"block_e_bwd": 128},
                       {"block_e_bwd": 512}, {"block_s": 256, "block_e_bwd": 128}):
            try:
                out["sweep"][json.dumps(blocks)] = times(**blocks)
            except Exception as err:  # noqa: BLE001 — a refused geometry is a finding, printed whole
                out["sweep"][json.dumps(blocks)] = f"{type(err).__name__}: {str(err)[:300]}"
            print(json.dumps(out["sweep"]), flush=True)
    worst = max(out["relative_difference"].values())
    out["ok"] = worst <= args.tolerance
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
