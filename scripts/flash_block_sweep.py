"""Empirical flash-attention block-size sweep on the live chip, and the
kernel-only row of PERF.md §5.

At 8k the attention kernels are most of the GPT-2-small step, so their
efficiency is the lever. This times the forward alone and forward+backward of
the shapes the cells use (head_dim 64) across (block_q, block_k) combinations,
printing one JSON line per config so the winner can be promoted to the
defaults. The backward is timed as the step runs it: through the custom VJP,
which at these lengths is ONE kernel (``flash_dkv``, ``dq`` riding its tile)
plus the per-row ``delta``; ``bwd_ms`` is forward+backward less forward.

Run: python scripts/flash_block_sweep.py [--seq 8192] [--reps 5]
     python scripts/flash_block_sweep.py --row   # [48, 8192, 64] and [384, 1024, 64] at the default blocks
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # run as a script from anywhere


def time_config(bh: int, seq: int, d: int, block_q: int | None, block_k: int | None, reps: int,
                k_extra: int = 16) -> dict:
    """Differenced in-program-scan timing: each
    measurement runs a k-iteration lax.scan inside one jit and the
    (k+1)-vs-1 difference cancels the per-dispatch overhead."""
    from jax import lax

    from dsml_tpu.ops.flash import flash_attention

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, bh, seq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (1, bh, seq, d), jnp.bfloat16)
    v = jax.random.normal(kv, (1, bh, seq, d), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block_q, block_k=block_k)

    def fwd_body(carry, _):
        q, k, v = carry
        out = fwd(q, k, v)
        return (q + 1e-3 * out, k, v), out[0, 0, 0, 0].astype(jnp.float32)

    def grad_body(carry, _):
        q, k, v = carry
        l, (dq, dk, dv) = jax.value_and_grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
        # chain grads into the next iteration so XLA cannot hoist or
        # dead-code any of the n backward passes (1e-3 keeps bf16
        # magnitudes sane)
        return (q + 1e-3 * dq, k + 1e-3 * dk, v + 1e-3 * dv), l

    def p50_per_iter(body):
        def make_run(n):
            return jax.jit(lambda q, k, v: lax.scan(body, (q, k, v), None, length=n)[1][-1])

        run1, runk = make_run(1), make_run(1 + k_extra)
        float(run1(q, k, v))
        float(runk(q, k, v))

        def p50_of(fn):
            ts = []
            for _ in range(reps):
                t0 = time.monotonic()
                float(fn(q, k, v))
                ts.append(time.monotonic() - t0)
            return float(np.percentile(ts, 50))

        return max((p50_of(runk) - p50_of(run1)) / k_extra, 1e-9)

    t0 = time.monotonic()
    fwd_s, both_s = p50_per_iter(fwd_body), p50_per_iter(grad_body)
    total_s = time.monotonic() - t0

    # analytic causal attention FLOPs: fwd = 2 ops/MAC x 2 dots (qk, pv)
    # x bh x seq^2/2 (causal) x d; bwd approximately 2x fwd by the standard
    # convention (benchmarks/flops.py::attention_train_flops counts the same)
    fwd_flops = 2 * 2 * bh * (seq * seq // 2) * d
    return {
        "block_q": block_q,
        "block_k": block_k,
        "bh": bh,
        "seq": seq,
        "p50_ms": round(both_s * 1e3, 3),
        "fwd_ms": round(fwd_s * 1e3, 3),
        "bwd_ms": round((both_s - fwd_s) * 1e3, 3),
        "tflops": round(3 * fwd_flops / both_s / 1e12, 1),
        "compile_and_time_s": round(total_s, 1),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--bh", type=int, default=12)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--row", action="store_true",
                    help="PERF.md §5's kernel-only row: the cells' two shapes at the default blocks")
    args = ap.parse_args()

    print(json.dumps({"device": str(jax.devices()[0])}))
    if args.row:
        for bh, seq in ((48, 8192), (384, 1024)):
            print(json.dumps(time_config(bh, seq, 64, None, None, args.reps)), flush=True)
        return
    combos = [
        (256, 256), (256, 512), (512, 256), (512, 512),
        (512, 1024), (1024, 512), (1024, 1024), (2048, 512), (512, 2048),
    ]
    best = None
    for bq, bk in combos:
        if bq > args.seq or bk > args.seq:
            continue
        try:
            row = time_config(args.bh, args.seq, args.d, bq, bk, args.reps)
        except Exception as e:  # a combo can exceed VMEM — record and move on
            row = {"block_q": bq, "block_k": bk, "error": repr(e)[:120]}
        print(json.dumps(row), flush=True)
        if "p50_ms" in row and (best is None or row["p50_ms"] < best["p50_ms"]):
            best = row
    print(json.dumps({"best": best}))


if __name__ == "__main__":
    main()
