"""The loss head alone, on the chip, at the cells' shapes: ``jax.value_and_grad`` of
``ops/xent.py`` beside the form it replaced (PR 30), values and time.

    chiprun -- python3 scripts/xent_head_check.py [--sweep]

The three shapes are the cells' heads, bf16 hidden states and weights:
``[32768, 768]`` × 50,257 (``gpt2s-1k``, ``gpt2s-8k``), ``[4096, 1280]`` × 50,257
(``gpt2l-1k``, per chip at dp=4 too) and ``[8192, 2560]`` × 65,536 (``jamba2-3b-8k``).
``vocab_scan`` below is a frozen copy of the parent's form: the vocabulary in chunks
of 8,192 (padded to a multiple), a running log-sum-exp, and a backward that computes
every chunk's logits again: four padded head matmuls where the blocked sweep does
three. Both get the same inputs in one process. Time is the device's, from a
``jax.profiler`` trace of ``--calls`` calls after two warm ones (the union of the
device's op intervals; the host clock where the trace holds no device plane, as in
a CPU rehearsal: ``--rehearse``). ``--sweep`` also times the sweep at other block
budgets (``ops/xent.py::_HEAD_BYTES``), which is how that constant was sized. The
last line is one JSON object; exit 1 where loss or a gradient differs from the
parent's by more than ``--tolerance`` of the parent's largest magnitude.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

SHAPES = {"gpt2s": (32768, 768, 50257), "gpt2l": (4096, 1280, 50257), "jamba2-3b": (8192, 2560, 65536)}
TRACE_DIR = REPO / ".bench_trace" / "xent_head"


def vocab_scan(chunk: int = 8192):
    """The parent's head (``dsml_tpu/ops/xent.py`` before PR 30), frozen."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def chunks(wte):
        v = wte.shape[0]
        n_chunks = -(-v // chunk)
        wte = jnp.pad(wte, ((0, n_chunks * chunk - v), (0, 0)))
        return wte.reshape(n_chunks, chunk, -1), jnp.arange(n_chunks), v

    def logits_of(h32, w_c, c_idx, v):
        logits = h32 @ w_c.astype(jnp.float32).T
        col = c_idx * chunk + jnp.arange(chunk)
        return jnp.where(col[None, :] < v, logits, -jnp.inf), col

    def forward(h, wte, targets):
        n, h32 = h.shape[0], h.astype(jnp.float32)
        w_chunks, idx, v = chunks(wte)

        def body(carry, inputs):
            m, s, tgt = carry
            logits, _ = logits_of(h32, *inputs, v)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(logits - m_new[:, None]), axis=-1)
            local = targets - inputs[1] * chunk
            in_c = (local >= 0) & (local < chunk)
            safe = jnp.clip(local, 0, chunk - 1)
            tgt = tgt + jnp.where(in_c, jnp.take_along_axis(logits, safe[:, None], 1)[:, 0], 0.0)
            return (m_new, s, tgt), None

        init = (jnp.full((n,), -jnp.inf, jnp.float32), jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
        (m, s, tgt), _ = lax.scan(body, init, (w_chunks, idx))
        lse = m + jnp.log(s)
        return lse - tgt, lse

    @jax.custom_vjp
    def per_row(h, wte, targets):
        return forward(h, wte, targets)[0]

    def fwd_rule(h, wte, targets):
        loss, lse = forward(h, wte, targets)
        return loss, (h, wte, targets, lse)

    def bwd_rule(res, g):
        h, wte, targets, lse = res
        h32 = h.astype(jnp.float32)
        w_chunks, idx, v = chunks(wte)

        def body(dh, inputs):
            logits, col = logits_of(h32, *inputs, v)
            p = jnp.exp(logits - lse[:, None])
            ds = (p - (col[None, :] == targets[:, None])) * g.astype(jnp.float32)[:, None]
            return dh + ds @ inputs[0].astype(jnp.float32), ds.T @ h32

        dh, dw_chunks = lax.scan(body, jnp.zeros_like(h32), (w_chunks, idx))
        return dh.astype(h.dtype), dw_chunks.reshape(-1, h.shape[1])[:v].astype(wte.dtype), None

    per_row.defvjp(fwd_rule, bwd_rule)
    return lambda h, wte, targets: per_row(h, wte, targets).mean()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES), choices=list(SHAPES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--tolerance", type=float, default=1e-2,
                    help="largest |change - parent| / max|parent| allowed (bf16 gradients: 2^-8 a rounding)")
    ap.add_argument("--sweep", action="store_true", help="also time other block budgets")
    ap.add_argument("--rehearse", action="store_true", help="tiny shapes, for a run without the chip")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks import trace_reduce
    from dsml_tpu.ops import xent

    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform}), flush=True)

    def per_call_ms(fn, operands, label):
        for _ in range(2):
            jax.block_until_ready(fn(*operands))
        where = TRACE_DIR / label
        shutil.rmtree(where, ignore_errors=True)
        t0 = time.perf_counter()
        with jax.profiler.trace(str(where)):
            for _ in range(args.calls):
                jax.block_until_ready(fn(*operands))
        host_ms = (time.perf_counter() - t0) * 1e3 / args.calls
        ops = next(iter(trace_reduce.load(str(where))["devices"].values()), None)
        if not ops:
            return host_ms, "host_clock"
        busy = trace_reduce.length(trace_reduce.union([[e[2], e[2] + e[3]] for e in ops]))
        return busy / 1e6 / args.calls, "device_trace"

    def head(loss):
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

    out, ok = {}, True
    for name in args.shapes:
        n, d, v = (96, 64, 1000) if args.rehearse else SHAPES[name]
        ks = jax.random.split(jax.random.key(args.seed), 3)
        h = jax.random.normal(ks[0], (n, d)).astype(jnp.bfloat16)
        wte = (0.02 * jax.random.normal(ks[1], (v, d))).astype(jnp.bfloat16)
        targets = jax.random.randint(ks[2], (n,), 0, v)
        operands = (h, wte, targets)
        parent, change = head(vocab_scan(256 if args.rehearse else 8192)), head(xent.chunked_softmax_xent)
        (l_p, g_p), (l_c, g_c) = parent(*operands), change(*operands)
        diff = {"loss": abs(float(l_c) - float(l_p)) / abs(float(l_p))}
        for key, a, b in zip(("dh", "dw"), g_c, g_p):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            diff[key] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        del g_p, g_c
        p_ms, clock = per_call_ms(parent, operands, f"{name}-parent")
        c_ms, _ = per_call_ms(change, operands, f"{name}-change")
        row = {"shape": [n, d, v], "blocks_rows": xent.block_rows(n, v, d), "clock": clock, "parent_ms": p_ms,
               "change_ms": c_ms, "ratio": c_ms / p_ms, "relative_difference": diff}
        if args.sweep:
            kept, row["sweep"] = xent._HEAD_BYTES, {}
            for mib in (384, 512, 768, 1024, 1280, 1536, 2048):
                xent._HEAD_BYTES = mib << 20
                blocks = xent.block_rows(n, v, d)
                if str(blocks) not in row["sweep"]:
                    ms, _ = per_call_ms(head(xent.chunked_softmax_xent), operands, f"{name}-{mib}")
                    row["sweep"][str(blocks)] = {"budget_mib": mib, "ms": ms, "ratio": ms / p_ms}
            xent._HEAD_BYTES = kept
        ok = ok and max(diff.values()) <= args.tolerance
        out[name] = row
        print(json.dumps({name: row}), flush=True)
    out["ok"] = ok
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
