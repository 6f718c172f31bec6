"""Headline benchmark: GPT-2-small (125M) training throughput + MFU per chip.

The flagship config (BASELINE.md config #5: "TinyStories GPT-2-small (125M),
data-parallel + grad accumulation") is what actually exercises the MXU, so it
is the headline metric. The step is a fully device-resident jitted program:
bf16 params/activations, the Pallas flash-attention kernel at the
auto-swept blocks (512×512 short, 1024×1024 at len≥4096 — probed 1.7-2×
faster than XLA's fused attention once the blocks are MXU-sized),
dense-logits cross-entropy (beats the chunked stream at seq=1024; the
chunked path serves configs where [tokens, vocab] doesn't fit), adamw with
donated params/opt_state. A second row trains at seq=8192 — a length where
XLA's fused attention fails to compile outright — as the long-context
evidence.

MFU = achieved matmul FLOP/s ÷ the chip's peak bf16 FLOP/s, with FLOPs
counted analytically (6·N per token for param matmuls + the causal
attention term) — the standard PaLM-appendix accounting.

Secondary sections: the MNIST MLP ladder config (with honest data-provenance
labels — the reference's 60k train blob is stripped from the mirror, so the
accuracy protocol differs), and AllReduceRing p50 (1 MB payload) on the real
chip plus on an 8-device virtual CPU mesh (harness proof that the ring
actually hops; a 1-chip "ring" has none).

Prints exactly one JSON line:
    {"metric": "gpt2_tokens_per_sec_per_chip", "value": N,
     "unit": "tokens/s/chip", "vs_baseline": N, "extras": {...}}

``vs_baseline`` compares achieved training FLOP/s against the reference's
achieved FLOP/s (MLP 101,770 params × 1,250 samples/s × 6 FLOP/param/sample —
its only published throughput, BASELINE.md); per-workload ratios that would
be apples-to-oranges are suppressed and labeled in extras instead.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, ".")

# Soft wall-clock budget: the HEADLINE section always runs, and each optional
# section first checks the remaining budget so a tight budget degrades to
# fewer rows instead of a run cut mid-section.
_T0 = time.monotonic()


def _env_float(name: str, default: float) -> float:
    """One place for the malformed-env-var-must-not-cost-the-JSON-line
    policy every BENCH_* knob shares."""
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


_BUDGET_S = _env_float("BENCH_BUDGET_S", 1320.0)


def _budget_left() -> float:
    return _BUDGET_S - (time.monotonic() - _T0)


def _skip_for_budget(extras: dict, key: str, need_s: float) -> bool:
    left = _budget_left()
    if left < need_s:
        extras[f"{key}_skipped"] = (
            f"bench time budget: {left:.0f}s left < {need_s:.0f}s this section needs"
        )
        return True
    return False


REFERENCE_SAMPLES_PER_SEC = 1250.0  # 60k × 10 epochs / ~480 s (BASELINE.md)
REFERENCE_RING_MS = 8.0  # reference ring all-reduce step, 1 MB × 3 simulated devices
REFERENCE_MLP_PARAMS = 101_770  # client.go:23-26
# the reference's achieved training FLOP/s: 6 FLOP/param/sample (fwd 2 + bwd 4)
REFERENCE_FLOPS_PER_SEC = 6.0 * REFERENCE_MLP_PARAMS * REFERENCE_SAMPLES_PER_SEC

# peak bf16 FLOP/s by TPU generation (public spec sheets)
_PEAK_BF16 = {
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v6": 918e12,  # trillium
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def _peak_flops(device) -> float:
    """Peak bf16 FLOP/s of ``device``; a device kind the table does not
    know is an error, not a default."""
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in _PEAK_BF16.items():
        if key in kind:
            return peak
    raise ValueError(
        f"no peak bf16 FLOP/s known for device_kind {kind!r}; add it to "
        f"_PEAK_BF16 (known: {sorted(_PEAK_BF16)})"
    )


def _p50_wall(fn, reps: int = 5) -> float:
    """Median wall-clock seconds of ``fn()`` after one untimed warmup call
    (compile + cache). ``fn`` must force its own device sync.
    The ONE timing closure every simple bench row shares, so reps/percentile
    tweaks can't drift between rows."""
    import numpy as np

    fn()
    ts = []
    for _ in range(reps):
        t0 = time.monotonic()
        fn()
        ts.append(time.monotonic() - t0)
    return float(np.percentile(ts, 50))


def bench_gpt2() -> dict:
    """Flagship: GPT-2-small (125M) jitted train step — bf16, Pallas flash
    attention (hardware-swept auto blocks), dense-logit xent, adamw with
    donated state (the probed winners; see module docstring).
    Tokens/sec/chip + MFU, plus seq-8192 and seq-16384 long-context rows.
    Synthetic token data — throughput/MFU only, no quality claim (labeled
    in provenance)."""
    # each sub-row delegates to the SAME section helper the --section CLI
    # runs, so the full-run and resumable-capture paths cannot drift apart
    out = _section_gpt2_small()
    # long-context row: seq 8192 on one chip — the flash kernel's regime
    # (XLA's fused attention fails to compile at this length); chunked xent
    # keeps the [tokens, vocab] logits out of HBM
    if not _skip_for_budget(out, "gpt2_seq8k", 180):
        try:
            out.update(_section_gpt2_seq8k())
        except Exception as e:
            out["gpt2_seq8k_error"] = repr(e)[:200]
    # serving row: greedy KV-cache decode throughput (the reference has no
    # inference path at all)
    if not _skip_for_budget(out, "gpt2_decode", 180):
        try:
            out.update(bench_gpt2_decode())
        except Exception as e:
            out["gpt2_decode_error"] = repr(e)[:200]
    # scale row: GPT-2-medium (350M) — MFU climbs with model size (less of
    # the step is the small-matmul/vocab tail), the don't-stop-at-parity
    # evidence beyond the BASELINE flagship
    if not _skip_for_budget(out, "gpt2_medium", 300):
        try:
            out.update(_section_gpt2_medium())
        except Exception as e:
            out["gpt2_medium_error"] = repr(e)[:200]
    # scale stretch: GPT-2-large (774M) on one chip — the heaviest compile
    # in the bench, so it must not starve the rows above
    if not _skip_for_budget(out, "gpt2_large", 420):
        try:
            out.update(_section_gpt2_large())
        except Exception as e:
            out["gpt2_large_error"] = repr(e)[:200]
    # extreme scale: 1.5B on one chip via adafactor + remat
    if not _skip_for_budget(out, "gpt2_xl", 600):
        try:
            out.update(_section_gpt2_xl())
        except Exception as e:
            out["gpt2_xl_error"] = repr(e)[:200]
    # length stretches LAST: 16k (no remat) then 32k (remat) tokens in one
    # sequence, still single-chip — a tight budget must drop these before
    # the rows above
    if not _skip_for_budget(out, "gpt2_seq16k", 180):
        try:
            out.update(_section_gpt2_seq16k())
        except Exception as e:
            out["gpt2_seq16k_error"] = repr(e)[:200]
    if not _skip_for_budget(out, "gpt2_seq32k", 200):
        try:
            out.update(_section_gpt2_seq32k())
        except Exception as e:
            out["gpt2_seq32k_error"] = repr(e)[:200]
    return out


def bench_gpt2_decode() -> dict:
    """Greedy decode tokens/sec on the compiled prefill + KV-cache path:
    batch 8, prompt 128. Timing by differencing a long and a short generate
    (same prefill, same dispatch+fetch overhead — the difference is pure
    decode steps)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config

    batch, prompt_len = 8, 128
    cfg = dataclasses.replace(GPT2Config.small(), dtype="bfloat16", max_seq=1024)
    model = GPT2(cfg)
    dev = jax.devices()[0]
    params = jax.device_put(model.init(0), dev)
    rng = np.random.default_rng(0)
    prompt = jax.device_put(
        jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32), dev
    )

    n_short, n_long = 16, 144

    def timed(ps, n_new):  # D2H (np.asarray) forces the sync
        return _p50_wall(lambda: np.asarray(model.generate(ps, prompt, n_new)))

    per_step = (timed(params, n_long) - timed(params, n_short)) / (n_long - n_short)
    out = {
        "gpt2_decode_tokens_per_sec": round(batch / per_step, 1),
        "gpt2_decode_step_ms": round(per_step * 1e3, 3),
        "gpt2_decode_batch": batch,
        "gpt2_decode_prompt_len": prompt_len,
    }
    # weight-only int8 variant: decode is weight-HBM-bound, so halved
    # weight bytes should show directly in tokens/s (same differenced
    # methodology — the rows are directly comparable)
    try:
        from dsml_tpu.models.common import quantize_weights_int8

        # jnp ops follow their input's device: quantizing the device-
        # resident params directly avoids a full D2H+H2D round trip
        qp = quantize_weights_int8(params)
        per_q = (timed(qp, n_long) - timed(qp, n_short)) / (n_long - n_short)
        out.update({
            "gpt2_decode_wq8_tokens_per_sec": round(batch / per_q, 1),
            "gpt2_decode_wq8_step_ms": round(per_q * 1e3, 3),
            "gpt2_decode_wq8_speedup": round(per_step / per_q, 2),
        })
    except Exception as e:
        out["gpt2_decode_wq8_error"] = repr(e)[:200]
    # KV-cache quantization variants: the cache-read side of the decode
    # bandwidth story. These rows are what validates (or falsifies) the
    # int4-halves-the-int8-traffic claim on real hardware.
    for mode in ("int8", "int4"):
        try:
            qm = GPT2(dataclasses.replace(cfg, kv_quant=mode))

            def timed_kv(n_new):
                return _p50_wall(lambda: np.asarray(qm.generate(params, prompt, n_new)))

            per_kv = (timed_kv(n_long) - timed_kv(n_short)) / (n_long - n_short)
            out.update({
                f"gpt2_decode_kv{mode[3]}_tokens_per_sec": round(batch / per_kv, 1),
                f"gpt2_decode_kv{mode[3]}_speedup": round(per_step / per_kv, 2),
            })
        except Exception as e:
            out[f"gpt2_decode_kv{mode[3]}_error"] = repr(e)[:200]
    # batch-scaling row: decode at small batch is bound by reading every
    # param per step, so widening the batch amortizes that read — the
    # near-linear region is the serving-throughput headroom a deployment
    # gets by raising n_slots
    try:
        b64 = 64
        prompt64 = jax.device_put(
            jnp.asarray(rng.integers(0, cfg.vocab_size, (b64, prompt_len)), jnp.int32),
            dev,
        )

        def timed64(n_new):
            return _p50_wall(lambda: np.asarray(model.generate(params, prompt64, n_new)))

        per_64 = (timed64(n_long) - timed64(n_short)) / (n_long - n_short)
        out.update({
            "gpt2_decode_b64_tokens_per_sec": round(b64 / per_64, 1),
            "gpt2_decode_b64_step_ms": round(per_64 * 1e3, 3),
            "gpt2_decode_b64_scaling_vs_b8": round(
                (b64 / per_64) / (batch / per_step), 2),
        })
    except Exception as e:
        out["gpt2_decode_b64_error"] = repr(e)[:200]
    return out


def _timed_train_steps(model, optimizer, params, opt_state, x, y,
                       k_extra: int, reps: int, attn_impl: str = "flash"):
    """THE train-step timing harness, model-generic: one jitted program per
    run with k steps chained in a lax.scan, scalar-fetch sync,
    donation-chained reps, and the (1+k)-vs-1 difference — absolute time
    when the difference is non-positive.
    Returns (step_s, timing_mode, compile_s, final_loss). Every train
    throughput section MUST time through this function so the methodology
    cannot drift between model families."""
    import jax
    import numpy as np
    import optax
    from jax import lax

    def loss_fn(p):
        return model.loss_spmd(p, x, y, attn_impl=attn_impl)

    def train_step(carry, _):
        p, o = carry
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = optimizer.update(grads, o, p)
        return (optax.apply_updates(p, updates), o), loss

    def make_run(k):
        def run(p, o):
            (p, o), losses = lax.scan(train_step, (p, o), None, length=k)
            return p, o, losses[-1]

        return jax.jit(run, donate_argnums=(0, 1))

    run1, runk = make_run(1), make_run(1 + k_extra)
    t0 = time.monotonic()
    state1 = run1(params, opt_state)
    float(state1[2])
    statek = runk(*state1[:2])
    float(statek[2])
    compile_s = time.monotonic() - t0

    def p50(fn, state):
        # donation consumes the inputs — chain each rep off the previous
        # output (same shardings, so timing is steady-state)
        ts = []
        for _ in range(reps):
            t0 = time.monotonic()
            state = fn(*state[:2])
            float(state[2])
            ts.append(time.monotonic() - t0)
        return float(np.percentile(ts, 50)), state

    tk, statek = p50(runk, statek)
    t1, state1 = p50(run1, statek)
    loss = float(state1[2])
    if tk - t1 > 1e-3:
        step_s = (tk - t1) / k_extra
        timing_mode = "differenced"  # per-dispatch overhead cancelled
    else:
        step_s = tk / (1 + k_extra)
        timing_mode = "absolute"
    return step_s, timing_mode, compile_s, loss


def _gpt2_train_throughput(
    batch: int, seq: int, xent_chunk: int, k_extra: int = 4, reps: int = 10,
    preset: str = "small", optimizer: str = "adamw", remat: bool = False,
) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config

    # Tuned single-chip winners (probed on a v5e): batch 8 beats 16/32
    # per-token at seq 1024; the auto-swept Pallas flash blocks (512x512
    # short, 1024x1024 at len>=4096 — scripts/flash_block_sweep.py) beat
    # XLA fusion at every length; dense logits beat the chunked stream when
    # they fit; donating params+opt_state buys ~20% by letting XLA update
    # in place.
    cfg = dataclasses.replace(
        GPT2Config.by_name(preset), dtype="bfloat16", max_seq=seq,
        xent_chunk=xent_chunk, remat=remat,
    )
    model = GPT2(cfg)
    dev = jax.devices()[0]
    params = jax.device_put(model.init(0), dev)
    n_params = model.n_params(params)
    # adafactor: factored second moments hold O(rows + cols) state instead
    # of AdamW's two full f32 moment trees — what lets the 1.5B XL preset
    # fit a single 16 GB chip alongside bf16 params + grads
    if optimizer == "adafactor":
        optimizer = optax.adafactor(3e-4)
    elif optimizer == "adamw":
        optimizer = optax.adamw(3e-4, weight_decay=0.01)
    else:  # a typo must not silently bench the wrong optimizer under a
        # hardcoded section label
        raise ValueError(f"unknown optimizer {optimizer!r} (adamw | adafactor)")
    opt_state = jax.device_put(optimizer.init(params), dev)

    rng = np.random.default_rng(0)
    x = jax.device_put(
        jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32), dev
    )
    y = jnp.roll(x, -1, axis=1)

    step_s, timing_mode, compile_s, loss = _timed_train_steps(
        model, optimizer, params, opt_state, x, y, k_extra, reps
    )

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step / step_s

    # analytic matmul FLOPs per step (fwd; bwd = 2×fwd) — the shared
    # accounting in models/common (obs.step_stats derives MFU from the
    # same numerators, so bench and registry cannot drift)
    from dsml_tpu.models.common import transformer_train_flops
    from dsml_tpu.obs import mfu as _mfu

    d, ff, L, V = cfg.d_model, cfg.d_ff, cfg.n_layer, cfg.vocab_size
    T = tokens_per_step
    step_flops = transformer_train_flops(cfg, T, seq)
    fwd = step_flops // 3
    achieved_flops = step_flops / step_s
    peak = _peak_flops(dev)
    mfu = _mfu(achieved_flops, peak)

    # hardware MFU: what the chip actually executed, remat recompute
    # included (analytic MFU counts only the useful 3x-fwd FLOPs, so remat
    # rows read low — both numbers are stated)
    mfu_hw = None
    if remat:
        block_fwd = fwd - 2 * T * d * V  # unembedding is outside the blocks
        if remat == "mlp":
            recompute = L * 2 * 2 * T * d * ff  # FFN matmuls only
        else:  # True / "int8": whole-block forward re-runs in the backward
            recompute = block_fwd
        mfu_hw = (step_flops + recompute) / step_s / peak

    return {
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "step_ms": round(step_s * 1e3, 2),
        "achieved_tflops": round(achieved_flops / 1e12, 2),
        "peak_tflops": round(peak / 1e12, 1),
        "params": n_params,
        "batch": batch,
        "seq": seq,
        "dtype": "bfloat16",
        "attn": "pallas_flash_auto",  # swept blocks: 512x512 short, 1024x1024 at len>=4096
        "remat": remat,
        "mfu_hw": round(mfu_hw, 4) if mfu_hw is not None else None,
        "donate": True,
        "compile_s": round(compile_s, 1),
        "timing_mode": timing_mode,
        "final_loss": round(float(loss), 3),
    }


def bench_gpt2_realtext() -> dict:
    """REAL-TEXT quality row: train a byte-level GPT-2
    on genuine English prose (``utils.data.load_text_corpus`` — a user
    corpus at data/corpus.txt when present, else repo docs + stdlib/numpy
    docstrings) through ``lm_window_batches``, and report the loss
    trajectory plus held-out perplexity. This is a LEARNING demonstration,
    not a throughput row — the flagship MFU numbers stay on the synthetic
    (shape-controlled) stream. Sized down on a CPU backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.utils.data import (
        carve_lm_eval_split,
        lm_window_batches,
        load_text_corpus,
    )

    on_accel = jax.default_backend() not in ("cpu",)
    tokens, provenance = load_text_corpus()
    if on_accel:
        seq, batch, steps, n_layer, d_model, d_ff, dtype = 512, 32, 300, 4, 256, 1024, "bfloat16"
    else:
        seq, batch, steps, n_layer, d_model, d_ff, dtype = 128, 16, 120, 2, 128, 512, "float32"

    def train_eval(train_toks, eval_toks, vocab):
        """Train the row's architecture on pre-split (train, eval) ids and
        return (first_loss, final_loss, eval_loss|None, n_eval_targets) —
        shared by the byte-level and BPE variants so both run the same
        trunk/steps/batch/seq (the split happens OUTSIDE so the BPE variant
        can hold out the same text rather than re-carving in id space)."""
        cfg = GPT2Config(
            vocab_size=vocab, max_seq=seq, n_layer=n_layer, n_head=8,
            d_model=d_model, d_ff=d_ff, dtype=dtype, xent_chunk=0,
        )
        model = GPT2(cfg)
        dev = jax.devices()[0]
        optimizer = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(3e-4))
        params = jax.device_put(model.init(0), dev)
        opt_state = jax.device_put(optimizer.init(params), dev)

        @jax.jit
        def train_step(p, o, x, y):
            loss, grads = jax.value_and_grad(model.loss)(p, x, y)
            updates, o = optimizer.update(grads, o, p)
            return optax.apply_updates(p, updates), o, loss

        losses = []
        xb = yb = None
        for x, y in lm_window_batches(train_toks, seq, batch, seed=0, steps=steps):
            params, opt_state, loss = train_step(params, opt_state, x, y)
            losses.append(float(loss))
            xb, yb = x, y
        # steady-state step seconds — what the vocab size costs in
        # embed/unembed throughput at this trunk (d_model x vocab matmuls)

        def chain(k):
            p, o = params, opt_state
            t0 = time.monotonic()
            for _ in range(k):
                p, o, closs = train_step(p, o, xb, yb)
            float(closs)
            return time.monotonic() - t0

        chain(1)  # settle caches/queues
        # median of 3 differenced pairs (same policy as the serving drains)
        pairs = [(chain(8), chain(1)) for _ in range(3)]
        diffs = [(t8 - t1) / 7 for t8, t1 in pairs if t8 - t1 > 1e-3]
        if diffs:
            step_s, step_timing = float(np.median(diffs)), "differenced"
        else:  # jitter swamped every diff; absolute retains ~RTT/8 overhead
            step_s, step_timing = float(np.median([t8 / 8 for t8, _ in pairs])), "absolute"
        ev = None
        n_targets = 0
        if eval_toks is not None:
            # held-out loss on non-overlapping windows of the eval tail
            eval_loss_fn = jax.jit(model.loss)
            n_win = (len(eval_toks) - 1) // seq
            ev_losses = []
            for i in range(0, n_win - n_win % batch, batch):
                xs = np.stack(
                    [eval_toks[(i + j) * seq : (i + j) * seq + seq] for j in range(batch)]
                ).astype(np.int32)
                ys = np.stack(
                    [eval_toks[(i + j) * seq + 1 : (i + j) * seq + seq + 1] for j in range(batch)]
                ).astype(np.int32)
                ev_losses.append(float(eval_loss_fn(params, xs, ys)))
                n_targets += batch * seq
            if ev_losses:
                ev = float(np.mean(ev_losses))
        return (float(np.mean(losses[:10])), float(np.mean(losses[-10:])),
                ev, n_targets, step_s, step_timing)

    train_b, eval_b = carve_lm_eval_split(tokens.astype(np.int32), seq, batch)
    first, final, ev, _, byte_step_s, byte_step_timing = train_eval(train_b, eval_b, 256)
    out = {
        "gpt2_realtext_first_loss": round(first, 4),
        "gpt2_realtext_final_loss": round(final, 4),
        "gpt2_realtext_steps": steps,
        "gpt2_realtext_tokens_per_step": batch * seq,
        "gpt2_realtext_step_ms": round(byte_step_s * 1e3, 1),
        "gpt2_realtext_step_timing": byte_step_timing,
        "gpt2_realtext_corpus_bytes": int(len(tokens)),
        "gpt2_realtext_model": f"byte-GPT2 L{n_layer} d{d_model} seq{seq} {dtype}",
        "gpt2_realtext_provenance": provenance,
    }
    if ev is not None:
        out["gpt2_realtext_eval_loss"] = round(ev, 4)
        out["gpt2_realtext_eval_ppl"] = round(float(np.exp(ev)), 2)
        # bits/byte: the tokenizer-NEUTRAL quality metric (for byte-level
        # models each token is one byte, so bpb = loss / ln 2) — what makes
        # the BPE row below comparable to this one
        out["gpt2_realtext_eval_bpb"] = round(ev / float(np.log(2)), 4)

    # BPE variant at a MATCHED step budget (same trunk/steps/batch/seq;
    # the 2048-vocab embed/unembed adds ~14% step FLOPs at this d_model —
    # the standard larger-vocab cost, stated rather than hidden): each
    # position carries ~3 bytes of text, so the model sees ~3x more prose
    # per step; bpb on the SAME held-out text decides whether that buys
    # quality. The tokenizer trains on the TRAIN text only (no eval
    # leakage), and the bpb denominator is the eval windows' exact byte
    # count. Skipped when the budget is tight.
    def bpe_variant(vocab_target: int, prefix: str) -> None:
        """Train a BPE of ``vocab_target`` on the TRAIN text only, re-run
        the SAME trunk/steps/batch/seq on its ids, and report bpb on the
        same held-out text (exact target-byte normalization) plus the
        vocab's step-time cost vs the byte-level row."""
        from dsml_tpu.utils.tokenizer import BPETokenizer, padded_vocab

        train_text = bytes(train_b.astype(np.uint8)).decode("utf-8", errors="replace")
        eval_text = bytes(eval_b.astype(np.uint8)).decode("utf-8", errors="replace")
        tok = BPETokenizer.train(train_text, vocab_size=vocab_target)
        train_ids = tok.encode_array(train_text)
        eval_ids = tok.encode_array(eval_text)
        bytes_per_token = len(train_b) / max(len(train_ids), 1)
        bfirst, bfinal, bev, n_targets, bpe_step_s, bpe_step_timing = train_eval(
            train_ids, eval_ids, padded_vocab(tok.vocab_size)
        )
        out.update({
            f"{prefix}_vocab": tok.vocab_size,  # early-stop can land short
            f"{prefix}_vocab_target": vocab_target,
            f"{prefix}_bytes_per_token": round(bytes_per_token, 2),
            f"{prefix}_first_loss": round(bfirst, 4),
            f"{prefix}_final_loss": round(bfinal, 4),
            # the embed/unembed throughput cost of the larger vocab at this
            # trunk (matched steps/batch/seq — the honest price of bpb)
            f"{prefix}_step_ms": round(bpe_step_s * 1e3, 1),
            f"{prefix}_step_timing": bpe_step_timing,
            f"{prefix}_step_cost_vs_byte": round(
                bpe_step_s / max(byte_step_s, 1e-9), 2),
        })
        if bpe_step_timing != byte_step_timing:
            out[f"{prefix}_step_cost_note"] = (
                f"timing modes differ (byte {byte_step_timing}, this variant "
                f"{bpe_step_timing}) — the absolute side retains ~1/8 of a "
                "dispatch round trip, so the ratio is only indicative"
            )
        if bev is not None and n_targets:
            # exact per-byte normalization: total nats over the eval
            # windows' target tokens divided by those tokens' OWN byte
            # length (window i targets ids [i*seq+1, i*seq+seq])
            target_bytes = 0
            n_win_used = n_targets // seq
            for w in range(n_win_used):
                span = eval_ids[w * seq + 1 : w * seq + seq + 1]
                target_bytes += sum(len(tok.token_bytes(int(t))) for t in span)
            out[f"{prefix}_eval_loss"] = round(bev, 4)
            out[f"{prefix}_eval_bpb"] = round(
                bev * n_targets / max(target_bytes, 1) / float(np.log(2)), 4)
            out[f"{prefix}_eval_bytes_per_token"] = round(
                target_bytes / n_targets, 2)

    if eval_b is not None and not _skip_for_budget(out, "gpt2_realtext_bpe", 240):
        try:
            bpe_variant(2048, "gpt2_realtext_bpe")
        except Exception as e:
            out["gpt2_realtext_bpe_error"] = repr(e)[:200]
    # tokenizer at scale: a 16k vocab on the full prose corpus — where the
    # LM story stops being toy-scale. A CPU backend skips it (the 16k
    # trainer + third model train outweigh the row)
    if (eval_b is not None and on_accel
            and not _skip_for_budget(out, "gpt2_realtext_bpe16k", 420)):
        try:
            bpe_variant(16384, "gpt2_realtext_bpe16k")
        except Exception as e:
            out["gpt2_realtext_bpe16k_error"] = repr(e)[:200]
    return out


def bench_serving() -> dict:
    """Serving throughput rows: the continuous batcher
    under a streaming arrival mix vs a static padded batch on the SAME
    workload, plus decode throughput for a GQA + int8-KV Llama config.
    Sized down on a CPU backend (the provenance row says which shape
    ran)."""
    import jax
    import numpy as np

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.serving import ContinuousBatcher

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = dataclasses.replace(GPT2Config.small(), dtype="bfloat16", max_seq=1024)
        # quantum 16: each scheduler tick costs one host↔device round
        # trip, so the tick must carry enough decode work to amortize it —
        # the Orca iteration-level trade-off the batcher docstring describes
        n_requests, n_slots, quantum, chunk = 24, 8, 16, 256
        buckets = (128, 512)
        prompt_lo, prompt_hi, new_lo, new_hi = 16, 500, 16, 96
    else:
        cfg = GPT2Config(vocab_size=512, max_seq=256, n_layer=2, n_head=8,
                         d_model=128, d_ff=256)
        n_requests, n_slots, quantum, chunk = 10, 4, 4, 32
        buckets = (32, 128)
        prompt_lo, prompt_hi, new_lo, new_hi = 8, 120, 8, 24
    model = GPT2(cfg)
    params = jax.device_put(model.init(0), jax.devices()[0])
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, (int(l),)).astype(np.int32)
        for l in np.exp(rng.uniform(np.log(prompt_lo), np.log(prompt_hi), n_requests))
    ]
    budgets = rng.integers(new_lo, new_hi + 1, n_requests).tolist()

    def make_batcher():
        return ContinuousBatcher(
            model, params, n_slots=n_slots, prompt_buckets=buckets,
            decode_quantum=quantum, prefill_chunk=chunk,
        )

    # warmup on the SAME batcher instance that gets timed: the jitted
    # decode/chunk/insert programs are per-instance closures, so a fresh
    # batcher would re-compile inside the timed window (while the static
    # baseline's generate cache lives on the shared model — the comparison
    # must not hand the static path a warm cache and the batcher a cold one)
    srv = make_batcher()
    srv.submit(rng.integers(0, cfg.vocab_size, (prompt_lo,)).astype(np.int32), 2)
    srv.submit(rng.integers(0, cfg.vocab_size, (prompt_hi,)).astype(np.int32), 2)
    srv.run()
    srv.collect()
    srv.reset_latency_stats()  # warmup requests must not skew the percentiles

    # timed: streaming arrivals — a third of the requests queue up front (a
    # burst), the rest arrive on ONE fixed wall-clock timestamp list shared
    # by every scheduler variant (a per-tick stream would let each
    # scheduler's own step latency reshape its arrival process, so the
    # adaptive-vs-plain rows would compare mismatched workloads). The
    # cadence approximates the old stream's rate at the plain scheduler's
    # tick time; what matters is that it is IDENTICAL across variants.
    arrivals = list(zip(prompts, budgets))
    burst = n_requests // 3
    arrival_dt = 0.08 if on_tpu else 0.02  # ~half a plain tick per arrival
    arrival_times = [0.0] * burst + [
        (i + 1) * arrival_dt for i in range(n_requests - burst)
    ]

    def n_dispatches(batcher):
        # every host→device round trip the scheduler pays: decode ticks,
        # prefill calls, and cache-insert scatters
        return (batcher.n_plain_ticks + batcher.n_turbo_ticks
                + batcher.n_adaptive_ticks + batcher.n_prefill_dispatches
                + batcher.n_insert_dispatches)

    def run_streaming(batcher):
        d0 = n_dispatches(batcher)
        t0 = time.monotonic()
        i = 0
        while batcher.n_queued or batcher.n_active or batcher.n_pending or i < n_requests:
            now = time.monotonic() - t0
            while i < n_requests and arrival_times[i] <= now:
                p, n = arrivals[i]
                batcher.submit(p, n)
                i += 1
            if i < n_requests and not (
                batcher.n_queued or batcher.n_active or batcher.n_pending
            ):
                # drained before the next arrival is due: wait for it
                # instead of spinning empty ticks
                time.sleep(max(arrival_times[i] - (time.monotonic() - t0), 0.0))
                continue
            batcher.step()
        out = batcher.collect()
        wall = time.monotonic() - t0
        return out, wall, n_dispatches(batcher) - d0

    out, cont_wall, cont_disp = run_streaming(srv)
    total_tokens = sum(len(t) for t in out.values())
    assert len(out) == n_requests
    # online-serving latency percentiles over the timed streaming workload
    # (warmup requests excluded via the reset)
    latency = srv.latency_stats()

    # adaptive early-exit ticks on the SAME workload: the dispatch bill
    # collapses to ~O(retirements + admissions) — same tokens (pinned in
    # tests), fewer host round trips. A failure here must not cost the
    # rows already measured above (same policy as the turbo sub-row)
    adaptive_error = None
    k_max = min(int(2 ** np.ceil(np.log2(new_hi + 1))), cfg.max_seq)
    try:
        srv_a = ContinuousBatcher(
            model, params, n_slots=n_slots, prompt_buckets=buckets,
            prefill_chunk=chunk, adaptive_quantum=k_max,
        )
        srv_a.submit(rng.integers(0, cfg.vocab_size, (prompt_lo,)).astype(np.int32), 2)
        srv_a.submit(rng.integers(0, cfg.vocab_size, (prompt_hi,)).astype(np.int32), 2)
        srv_a.run()
        srv_a.collect()
        out_a, adapt_wall, adapt_disp = run_streaming(srv_a)
        adapt_tokens = sum(len(t) for t in out_a.values())
        if sorted(map(tuple, out_a.values())) != sorted(map(tuple, out.values())):
            raise AssertionError("adaptive ticks changed tokens")
    except Exception as e:
        adaptive_error = repr(e)[:200]

    # per-dispatch host round trip (compile-cached trivial program, scalar
    # fetch): the quantity that separates scheduler cost from compute cost
    import jax.numpy as jnp

    trivial = jax.jit(lambda x: x + 1.0)
    float(trivial(jnp.zeros(())))
    rtt_s = _p50_wall(lambda: float(trivial(jnp.zeros(()))), reps=7)

    # static baseline on the SAME workload: pad every prompt to the longest,
    # one generate per slot-sized batch, everyone waits for the longest
    # budget (what serving WITHOUT continuous batching costs)
    max_len = max(len(p) for p in prompts)
    max_new = max(budgets)
    group_sizes = {len(prompts[j : j + n_slots]) for j in range(0, n_requests, n_slots)}
    for gs in group_sizes:  # compile each static shape before timing
        np.asarray(model.generate(
            params, jnp.asarray(np.zeros((gs, max_len), np.int32)), max_new))
    t0 = time.monotonic()
    got = 0
    for j in range(0, n_requests, n_slots):
        group = prompts[j : j + n_slots]
        batch = np.zeros((len(group), max_len), np.int32)
        for r, p in enumerate(group):
            batch[r, max_len - len(p):] = p  # left-pad: last position is real
        toks = np.asarray(model.generate(params, jnp.asarray(batch), max_new))
        got += sum(min(b, toks.shape[1]) for b in budgets[j : j + n_slots])
    static_wall = time.monotonic() - t0

    # decomposition: scheduler cost = dispatches × host RTT; compute cost =
    # what's left. The rtt0 model subtracts the measured per-dispatch round
    # trip from every wall — the workload-level comparison a host-local
    # deployment (RTT ~0) would see, where the batcher's no-padding
    # advantage is the whole story
    n_static_disp = (n_requests + n_slots - 1) // n_slots
    static_rtt0 = max(static_wall - n_static_disp * rtt_s, 1e-6)
    cont_rtt0 = max(cont_wall - cont_disp * rtt_s, 1e-6)

    rows = {
        "serving_continuous_tokens_per_sec": round(total_tokens / cont_wall, 1),
        "serving_static_tokens_per_sec": round(got / static_wall, 1),
        "serving_speedup_vs_static": round(
            (total_tokens / cont_wall) / (got / static_wall), 2),
        # the dispatch decomposition: how many host
        # round trips each scheduler paid for the same tokens, and the
        # modeled RTT=0 speedup that isolates the workload-level win
        "serving_dispatches_plain": cont_disp,
        "serving_dispatches_static": n_static_disp,
        "serving_dispatches_per_token_plain": round(cont_disp / total_tokens, 3),
        "serving_host_rtt_ms": round(rtt_s * 1e3, 2),
        "serving_speedup_vs_static_rtt0_plain": round(
            (total_tokens / cont_rtt0) / (got / static_rtt0), 2),
        "serving_adaptive_quantum": k_max,
        "serving_requests": n_requests,
        "serving_total_tokens": total_tokens,
        "serving_slots": n_slots,
        "serving_decode_quantum": quantum,
        "serving_prefill_chunk": chunk,
        "serving_ttft_p50_ms": round(latency.get("ttft_p50_s", 0) * 1e3, 1),
        "serving_ttft_p99_ms": round(latency.get("ttft_p99_s", 0) * 1e3, 1),
        # per-EMISSION gaps (one emission = one decode quantum of tokens)
        "serving_gap_p50_ms": round(latency.get("gap_p50_s", 0) * 1e3, 2),
        "serving_gap_p99_ms": round(latency.get("gap_p99_s", 0) * 1e3, 2),
        "serving_e2e_p99_ms": round(latency.get("e2e_p99_s", 0) * 1e3, 1),
        "serving_model": (
            f"GPT2 L{cfg.n_layer} d{cfg.d_model} max_seq{cfg.max_seq} {cfg.dtype}"
        ),
        "serving_note": (
            "continuous batching pays one host dispatch per scheduler tick; "
            "the static baseline decodes its whole budget inside one jitted "
            "scan. adaptive_quantum (early-exit device loop) collapses the "
            "dispatch bill to ~retirements+admissions; the residual gap to "
            "static is the per-dispatch host RTT (serving_host_rtt_ms × "
            "serving_dispatches_*), which the _rtt0 rows subtract to show "
            "the workload-level (no-padding) win a host-local deployment "
            "sees"
        ),
    }
    if adaptive_error is None:
        adapt_rtt0 = max(adapt_wall - adapt_disp * rtt_s, 1e-6)
        rows.update({
            "serving_adaptive_tokens_per_sec": round(adapt_tokens / adapt_wall, 1),
            "serving_adaptive_speedup_vs_static": round(
                (adapt_tokens / adapt_wall) / (got / static_wall), 2),
            "serving_dispatches_adaptive": adapt_disp,
            "serving_dispatches_per_token_adaptive": round(
                adapt_disp / adapt_tokens, 3),
            "serving_speedup_vs_static_rtt0": round(
                (adapt_tokens / adapt_rtt0) / (got / static_rtt0), 2),
        })
    else:
        rows["serving_adaptive_error"] = adaptive_error
    rows.update(_bench_serving_turbo(model, params, cfg, on_tpu))
    rows.update(_bench_serving_llama_kvquant(on_tpu))
    rows.update(_bench_speculative(model, params, on_tpu))
    return rows


def _bench_serving_turbo(model, params, cfg, on_tpu: bool) -> dict:
    """Turbo-tick escalation on a LONG-GENERATION workload (short prompts,
    large budgets — the shape where steady-state decode dominates and the
    per-tick dispatch RTT is the bottleneck): the same drain timed with
    turbo off vs on. The streaming row above keeps small mixed budgets
    where turbo rarely engages; this row is the one it exists for."""
    import numpy as np

    from dsml_tpu.serving import ContinuousBatcher

    if on_tpu:
        # n_requests == n_slots: everyone admits in the first tick and the
        # rest of the drain is pure steady-state decode — the regime the
        # escalation targets (with a standing queue the admission cadence
        # correctly keeps turbo off)
        n_requests, n_slots, quantum, factor = 8, 8, 16, 4
        new_lo, new_hi = 128, 192
    else:
        n_requests, n_slots, quantum, factor = 4, 4, 4, 4
        new_lo, new_hi = 24, 40
    rng = np.random.default_rng(7)
    max_prompt = min(64, cfg.max_seq - new_hi - 1)
    prompts = [
        rng.integers(0, cfg.vocab_size, (int(l),)).astype(np.int32)
        for l in rng.integers(8, max_prompt + 1, n_requests)
    ]
    budgets = rng.integers(new_lo, new_hi + 1, n_requests).tolist()

    def make_srv(turbo=0, adaptive=0):
        srv = ContinuousBatcher(
            model, params, n_slots=n_slots, prompt_buckets=(max(64, max_prompt),),
            decode_quantum=quantum if not adaptive else 1,
            turbo_factor=turbo, adaptive_quantum=adaptive,
        )
        # warmup must compile EVERY decode program the timed drain can hit:
        # with turbo, the first tick after prefill escalates (remaining
        # budget = quantum*(turbo+1)) and the leftover quantum drains
        # through a PLAIN tick; with adaptive, one early-exit tick covers it
        srv.submit(prompts[0], quantum * (max(turbo, 1) + 1) + 1)
        srv.run()
        srv.collect()
        return srv

    def drain(srv):
        d0 = (srv.n_plain_ticks + srv.n_turbo_ticks + srv.n_adaptive_ticks)
        for p, n in zip(prompts, budgets):
            srv.submit(p, int(n))
        t0 = time.monotonic()
        out = srv.run()
        wall = time.monotonic() - t0
        toks = sum(len(t) for t in out.values())
        ticks = (srv.n_plain_ticks + srv.n_turbo_ticks + srv.n_adaptive_ticks) - d0
        return toks / wall, ticks

    # repeat each drain and take the MEDIAN: a single drain spans only a
    # handful of dispatches, so one jittery dispatch could move a
    # single-shot ratio well beyond its real value
    reps = 3
    try:
        k_max = min(int(2 ** np.ceil(np.log2(new_hi + 1))), cfg.max_seq)
        runs = {}
        for name, kw in (("base", {}), ("turbo", {"turbo": factor}),
                         ("adaptive", {"adaptive": k_max})):
            srv = make_srv(**kw)  # one instance per mode: compile once,
            samples = [drain(srv) for _ in range(reps)]  # then drain reps×
            runs[name] = (
                float(np.median([s[0] for s in samples])),
                int(np.median([s[1] for s in samples])),
            )
    except Exception as e:  # never fail the whole serving section on this row
        return {"serving_turbo_error": repr(e)[:200]}
    base_tps, base_ticks = runs["base"]
    turbo_tps, turbo_ticks = runs["turbo"]
    adapt_tps, adapt_ticks = runs["adaptive"]
    return {
        "serving_longgen_tokens_per_sec": round(base_tps, 1),
        "serving_longgen_turbo_tokens_per_sec": round(turbo_tps, 1),
        "serving_longgen_adaptive_tokens_per_sec": round(adapt_tps, 1),
        "serving_turbo_speedup": round(turbo_tps / base_tps, 2),
        "serving_adaptive_longgen_speedup": round(adapt_tps / base_tps, 2),
        "serving_turbo_factor": factor,
        "serving_longgen_base_dispatches": base_ticks,
        "serving_longgen_turbo_dispatches": turbo_ticks,
        "serving_longgen_adaptive_dispatches": adapt_ticks,
        "serving_longgen_budget_range": [new_lo, new_hi],
        "serving_longgen_repeats": reps,
    }


def _bench_speculative(model, params, on_tpu: bool) -> dict:
    """Prompt-lookup speculative decode vs plain greedy generate on the
    same prompt: wall-clock ratio plus the verify-call count (the
    workload-independent diagnostic — tokens per HBM sweep). Random-init
    greedy output is degenerate/repetitive, i.e. lookup-FRIENDLY; the
    call count says how much acceptance this workload actually had, so
    the row can't oversell."""
    import jax.numpy as jnp
    import numpy as np

    from dsml_tpu.models.speculative import generate_speculative

    cfg = model.config
    rng = np.random.default_rng(2)
    if on_tpu:
        t, max_new, window, batch = 128, 256, 8, 8
    else:
        t, max_new, window, batch = 32, 48, 6, 2
    block = rng.integers(0, cfg.vocab_size, (t // 4,))
    prompt = jnp.asarray(np.tile(block, 4)[None, :].repeat(batch, 0), jnp.int32)

    greedy_s = _p50_wall(
        lambda: np.asarray(model.generate(params, prompt, max_new)), reps=3)
    spec_s = _p50_wall(
        lambda: np.asarray(generate_speculative(model, params, prompt, max_new,
                                                window=window)), reps=3)
    _, calls = generate_speculative(model, params, prompt, max_new,
                                    window=window, return_calls=True)
    total = batch * max_new
    return {
        "serving_spec_tokens_per_sec": round(total / spec_s, 1),
        "serving_spec_greedy_tokens_per_sec": round(total / greedy_s, 1),
        "serving_spec_speedup": round(greedy_s / spec_s, 2),
        "serving_spec_verify_calls": calls,
        "serving_spec_max_new": max_new,
        "serving_spec_tokens_per_call": round(max_new / max(calls, 1), 2),
        "serving_spec_window": window,
        "serving_spec_note": (
            "prompt-lookup speculative decode, whole loop in one jitted "
            "while_loop; tokens identical to greedy generate (pinned in "
            "tests). Acceptance is workload-dependent — the repetitive "
            "synthetic stream here is lookup-friendly, and "
            "tokens_per_call reports the actual acceptance"
        ),
    }


def _bench_serving_llama_kvquant(on_tpu: bool) -> dict:
    """Decode throughput for the GQA + int8 KV cache serving config —
    the memory-bound regime where kv_quant halves cache traffic."""
    import jax
    import numpy as np

    from dsml_tpu.models.llama import Llama, LlamaConfig
    from dsml_tpu.serving import ContinuousBatcher

    if on_tpu:
        # a ~200M GQA shape rather than TinyLlama-1.1B: the row's signal
        # (GQA + int8 KV decode throughput) doesn't need the extra 900M
        # params
        cfg = LlamaConfig(
            n_layer=12, n_head=16, n_kv_head=4, d_model=1024, d_ff=2816,
            max_seq=1024, dtype="bfloat16", kv_quant=True,
        )
        n_slots, quantum, n_new, prompt_len = 8, 8, 64, 128
    else:
        cfg = dataclasses.replace(
            LlamaConfig.tiny(), max_seq=256, kv_quant=True
        )
        n_slots, quantum, n_new, prompt_len = 4, 4, 16, 32
    model = Llama(cfg)
    params = jax.device_put(model.init(0), jax.devices()[0])
    rng = np.random.default_rng(1)

    # turbo: the drain is all-slots-at-once steady-state decode, exactly
    # the escalation's regime — dispatches drop ~turbo x after admission
    turbo = 4
    srv = ContinuousBatcher(
        model, params, n_slots=n_slots, prompt_buckets=(prompt_len,),
        decode_quantum=quantum, turbo_factor=turbo,
    )

    def run_once(n_tokens):
        # same instance for warmup and timing: the jitted programs are
        # per-batcher closures, and run()+collect() leaves it reusable
        for _ in range(n_slots):
            srv.submit(rng.integers(0, cfg.vocab_size, (prompt_len,))
                       .astype(np.int32), n_tokens)
        out = srv.run()
        return sum(len(t) for t in out.values())

    run_once(quantum * (turbo + 1) + 1)  # compile prefill + BOTH decode programs
    t0 = time.monotonic()
    total = run_once(n_new)
    wall = time.monotonic() - t0
    return {
        "serving_llama_kvquant_decode_tokens_per_sec": round(total / wall, 1),
        "serving_llama_kvquant_model": (
            f"Llama L{cfg.n_layer} d{cfg.d_model} q{cfg.n_head}/kv{cfg.n_kv_head} "
            f"int8-kv {cfg.dtype}"
        ),
        "serving_llama_kvquant_slots": n_slots,
        "serving_llama_kvquant_new_tokens": n_new,
        "serving_llama_kvquant_turbo_factor": turbo,
    }


def _differenced_ring_p50(mesh, algorithm: str, reps: int = 50, r_hi: int = 20) -> float:
    """p50 per-collective latency of the jitted all-reduce program on
    ``mesh`` (1 MB/device payload), with per-dispatch overhead cancelled.

    Per-dispatch overhead would swamp a sub-ms collective, so time R
    chained collectives in ONE program
    for R=1 and R=r_hi and difference. This is the SAME program the gRPC
    coordinator dispatches (collectives._stacked_all_reduce_fn), so the
    bench measures the production path. Shared by the real-chip and
    virtual-8-CPU sections so the methodology cannot drift between them."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dsml_tpu.ops.collectives import ReduceOp, _stacked_all_reduce_fn

    n = len(mesh.devices.flat)
    payload = np.zeros((n, 262_144), np.float32)  # 1 MB per device

    def p50_of(r):
        fn = _stacked_all_reduce_fn(mesh, "dp", ReduceOp.SUM, algorithm, repeats=r)
        # the jit donates its input; chain outputs (same sharding) instead of
        # reusing one buffer. SUM over zeros stays zeros, so values are stable.
        x = jax.device_put(payload, NamedSharding(mesh, P("dp")))
        x = fn(x)
        float(x[0, 0])  # compile + first run; scalar fetch forces the sync
        ts = []
        for _ in range(reps):
            t0 = time.monotonic()
            x = fn(x)
            float(x[0, 0])
            ts.append((time.monotonic() - t0) * 1e3)
        return float(np.percentile(ts, 50))

    return max((p50_of(r_hi) - p50_of(1)) / (r_hi - 1), 0.0)


def bench_ring_allreduce() -> dict:
    """AllReduceRing p50 latency, 1 MB payload — the second half of the
    BASELINE metric. Times the coordinator's jitted ring program
    (``make_stacked_all_reduce``: one H2D, the full 2(n−1)-step ppermute
    ring on-device, one D2H) over every local device."""
    import jax
    import numpy as np

    from dsml_tpu.ops.collectives import ReduceOp, make_stacked_all_reduce
    from dsml_tpu.parallel.mesh import build_mesh, MeshSpec

    devices = jax.devices()
    n = len(devices)
    mesh = build_mesh(MeshSpec(dp=n), devices)
    payload = np.zeros((n, 262_144), np.float32)  # 1 MB per device
    reps = 50

    # (a) device-resident ring alone — the "ring latency from real ICI"
    # number BASELINE.json asks for
    p50 = _differenced_ring_p50(mesh, "ring")
    # naive (gather-everything) baseline on the same payload — the 83 ms vs
    # 8 ms story the reference benchmarked (BASELINE.md), now from real
    # collectives — plus the bidirectional ring (full-duplex ICI)
    naive_p50 = _differenced_ring_p50(mesh, "naive")
    ring2_p50 = _differenced_ring_p50(mesh, "ring2")

    # (b) the full proto-API path the gRPC coordinator pays: H2D + ring + D2H
    # (np.asarray forces the D2H copy; block_until_ready alone would not)
    run = make_stacked_all_reduce(mesh, ReduceOp.SUM, algorithm="ring", axis_name="dp")
    np.asarray(run(payload))
    e2e_times = []
    for _ in range(reps):
        t0 = time.monotonic()
        np.asarray(run(payload))
        e2e_times.append((time.monotonic() - t0) * 1e3)
    e2e_p50 = float(np.percentile(e2e_times, 50))

    # decompose the e2e number: time the H2D placement and the D2H fetch
    # separately — the residual vs (a) is per-call dispatch, not the
    # collective. Uses the same sharding the e2e path places to.
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax as _jax

    sh = NamedSharding(mesh, P("dp"))
    h2d_times, d2h_times = [], []
    dev_buf = _jax.device_put(payload, sh)
    for _ in range(reps):
        t0 = time.monotonic()
        dev_buf = _jax.device_put(payload, sh)
        float(dev_buf[0, 0])
        h2d_times.append((time.monotonic() - t0) * 1e3)
        t0 = time.monotonic()
        np.asarray(dev_buf)
        d2h_times.append((time.monotonic() - t0) * 1e3)
    h2d_p50 = float(np.percentile(h2d_times, 50))
    d2h_p50 = float(np.percentile(d2h_times, 50))

    out = {
        "allreduce_ring_p50_ms": round(p50, 3),
        "allreduce_ring2_p50_ms": round(ring2_p50, 3),
        "allreduce_naive_p50_ms": round(naive_p50, 3),
        "allreduce_e2e_p50_ms": round(e2e_p50, 3),
        "allreduce_e2e_h2d_p50_ms": round(h2d_p50, 3),
        "allreduce_e2e_d2h_p50_ms": round(d2h_p50, 3),
        # what's left after transfers + the on-device collective: per-call
        # dispatch — the decomposition of the e2e row
        "allreduce_e2e_residual_ms": round(
            max(e2e_p50 - h2d_p50 - d2h_p50 - p50, 0.0), 3),
        "allreduce_payload_mb": 1.0,
        "allreduce_devices": n,
        "reference_ring_ms": REFERENCE_RING_MS,
        # on a single chip the ring has no hops (p50 ~ 0); rate vs the
        # reference only when there's a real ring to measure
        "allreduce_vs_baseline": round(REFERENCE_RING_MS / p50, 2) if p50 > 1e-3 else None,
    }
    if n == 1:
        out["allreduce_note"] = (
            "1 device: ring has zero hops and sub-resolution latencies are "
            "reported as measured; see allreduce_virtual8_* for a ring that hops"
        )
    return out


def _virtual8_main() -> None:
    """Subprocess entry: measure the ring on an 8-device virtual CPU mesh
    with the SAME ``_differenced_ring_p50`` harness as the real-chip section
    (shorter reps — CPU collectives are ms-scale, jitter-free enough)."""
    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    import jax

    from dsml_tpu.parallel.mesh import build_mesh, MeshSpec

    mesh = build_mesh(MeshSpec(dp=8), jax.devices()[:8])
    ring = _differenced_ring_p50(mesh, "ring", reps=20, r_hi=10)
    ring2 = _differenced_ring_p50(mesh, "ring2", reps=20, r_hi=10)
    naive = _differenced_ring_p50(mesh, "naive", reps=20, r_hi=10)

    # full proto-API path: gRPC client → coordinator → zero-copy HBM ring.
    # On this CPU mesh the number mostly shows the control-plane cost (device
    # "HBM" is host memory here); on real chips it tracks that the data
    # plane stays off the host. Failures here must not discard the ring/naive
    # numbers already measured above.
    wire_e2e = None
    wire_err = None
    coordinator, devices = None, []
    try:
        import numpy as np

        from dsml_tpu.comm.client import GRAD_ADDR, PipelineClient
        from dsml_tpu.comm.coordinator import CoordinatorConfig, serve_coordinator
        from dsml_tpu.comm.device_server import serve_local_devices

        devices = serve_local_devices(8, base_device_id=1, mem_size=0x800000)
        coordinator = serve_coordinator(config=CoordinatorConfig(health_interval_s=60))
        client = PipelineClient.connect(coordinator.address, [d.address for d in devices])
        payload = np.zeros(262_144, np.float32)  # 1 MB
        for rank in range(8):
            client.write(rank, GRAD_ADDR, payload.tobytes())
        client.all_reduce_ring(262_144 * 4)  # compile + warm
        ts = []
        for _ in range(20):
            t0 = time.monotonic()
            client.all_reduce_ring(262_144 * 4)
            ts.append((time.monotonic() - t0) * 1e3)
        wire_e2e = round(float(np.percentile(ts, 50)), 3)
    except Exception as e:
        wire_err = repr(e)[:200]
    finally:
        # servers must die even on failure, or their threads can outlive the
        # subprocess timeout and discard the ring/naive numbers printed below
        # (each stop individually guarded: one bad server must not keep the
        # rest alive or suppress the print)
        for handle in ([coordinator] if coordinator is not None else []) + list(devices):
            try:
                handle.stop()
            except Exception:
                pass

    out = {
        "ring_ms": round(ring, 3),
        "ring2_ms": round(ring2, 3),
        "naive_ms": round(naive, 3),
        "wire_e2e_ms": wire_e2e,
    }
    if wire_err:
        out["wire_e2e_error"] = wire_err
    print(json.dumps(out))


def _bucket_sweep_main() -> None:
    """Subprocess entry: gradient-bucketing sweep on the 8-device virtual
    CPU mesh — per-sync wall time for an 8 MiB synthetic gradient pytree
    across bucket sizes {1 buffer, 1, 4, 16 MiB} × {ring, q8}. The same
    differenced-repeats methodology as ``_differenced_ring_p50`` (chain R
    syncs in ONE program, difference R_hi vs 1) so per-dispatch overhead
    cancels. Relative signal only (CPU collectives, not ICI) — what it
    decides is the DSML_BUCKET_MB default's order of magnitude
    (docs/TUNING.md records the choice)."""
    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from dsml_tpu.ops.collectives import ReduceOp
    from dsml_tpu.parallel.bucketing import bucketed_all_reduce, plan_buckets
    from dsml_tpu.parallel.mesh import build_mesh, MeshSpec

    mesh = build_mesh(MeshSpec(dp=8), jax.devices()[:8])
    # 32 × 256 KiB f32 leaves (8 MiB): big enough that 1/4 MiB targets give
    # real bucket counts (8/2), small enough that the whole sweep lands in
    # ~2-3 min on the CPU mesh (a 32 MiB tree measured 4× slower). The
    # 16 MiB target exceeds the payload, so it coincides with 1buf here —
    # kept anyway: at training scale (100M+ params) it does not.
    rng = np.random.default_rng(0)
    tree = {
        f"w{i:02d}": jnp.asarray(rng.standard_normal(65_536), jnp.float32)
        for i in range(32)
    }
    total_bytes = 32 * 65_536 * 4
    r_hi, reps = 3, 3

    def per_sync_ms(algorithm, bucket_mb):
        def make(r):
            def per_rank(t):
                for _ in range(r):
                    t = bucketed_all_reduce(t, "dp", ReduceOp.AVG, algorithm, bucket_mb)
                return t

            return jax.jit(jax.shard_map(
                per_rank, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
            ))

        def p50_of(r):
            fn = make(r)
            out = fn(tree)
            float(out["w00"][0])  # compile + sync
            ts = []
            for _ in range(reps):
                t0 = time.monotonic()
                out = fn(out)
                float(out["w00"][0])
                ts.append((time.monotonic() - t0) * 1e3)
            return float(np.percentile(ts, 50))

        return max((p50_of(r_hi) - p50_of(1)) / (r_hi - 1), 0.0)

    rows = {"payload_mb": round(total_bytes / (1 << 20), 1), "devices": 8}
    for algorithm in ("ring", "q8"):
        for bucket_mb, label in ((None, "1buf"), (1, "1mb"), (4, "4mb"), (16, "16mb")):
            n_buckets = (
                1 if bucket_mb is None
                else plan_buckets(tree, bucket_mb).n_buckets
            )
            ms = per_sync_ms(algorithm, bucket_mb)
            rows[f"{algorithm}_{label}_ms"] = round(ms, 3)
            rows[f"{algorithm}_{label}_gbps"] = (
                round(total_bytes / (ms * 1e-3) / 1e9, 3) if ms > 0 else None
            )
            rows[f"{algorithm}_{label}_buckets"] = n_buckets
    print(json.dumps(rows))


def bench_bucket_sweep() -> dict:
    """Bucket-size sweep rows (virtual-8 mesh subprocess, same pattern as
    :func:`bench_ring_virtual8`): per-sync ms + achieved payload bytes/s per
    {bucket size} × {ring, q8} — the data the ``DSML_BUCKET_MB`` default is
    chosen from. Labeled virtual-CPU: relative signal, not ICI."""
    code = "import bench; bench._bucket_sweep_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=".",
            timeout=max(min(600.0, _budget_left()), 60.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "bucket_sweep_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {f"bucket_sweep_{k}": v for k, v in res.items()}
        out["bucket_sweep_note"] = (
            "8-device virtual CPU mesh: relative bucket-size signal for the "
            "DSML_BUCKET_MB default, not ICI bandwidth"
        )
        return out
    except Exception as e:  # never fail the bench on the secondary section
        return {"bucket_sweep_error": repr(e)[:200]}


def _quant_sweep_main() -> None:
    """Subprocess entry: the (bucket size × quant scheme × algorithm) grid
    on the 8-device virtual CPU mesh, plus the q8+EF loss-trajectory parity
    leg the acceptance bar pins.

    Grid cells reuse ``_bucket_sweep_main``'s differenced-repeats harness
    (chain R syncs in one program, difference R_hi vs 1) over an 8 MiB
    synthetic gradient tree. Every cell also reports its ANALYTIC per-rank
    wire bytes (static shapes ⇒ exact): the ``*_wire_reduction`` rows are
    quantized ÷ fp32 at equal bucket size — the ≥2× acceptance claim is a
    counting argument, not a CPU-timing one (CPU ppermute latency carries
    no ICI signal; the _ms cells are relative shape only, like the bucket
    sweep). The parity leg trains the reference MNIST-shaped MLP
    data-parallel on the virtual-8 mesh with fp32 ring vs q8_ring+EF vs
    q8_ring (no EF) and reports per-step relative deviation against the
    stated tolerance. ``DSML_QUANT_SWEEP_TINY=1`` shrinks the grid for the
    CI smoke step."""
    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from dsml_tpu.ops.collectives import ReduceOp, ring_wire_bytes
    from dsml_tpu.ops.quantization import quantized_ring_wire_bytes
    from dsml_tpu.parallel.bucketing import (
        QUANT_RING_ALGORITHMS,
        bucketed_all_reduce,
        init_error_feedback,
        plan_buckets,
    )
    from dsml_tpu.parallel.mesh import build_mesh, MeshSpec

    tiny = os.environ.get("DSML_QUANT_SWEEP_TINY") == "1"
    mesh = build_mesh(MeshSpec(dp=8), jax.devices()[:8])
    # same payload shape as the bucket sweep: 256 KiB f32 leaves
    n_leaves = 8 if tiny else 32
    rng = np.random.default_rng(0)
    tree = {
        f"w{i:02d}": jnp.asarray(rng.standard_normal(65_536), jnp.float32)
        for i in range(n_leaves)
    }
    total_elems = n_leaves * 65_536
    total_bytes = total_elems * 4
    r_hi, reps = (2, 2) if tiny else (3, 3)

    def per_sync_ms(algorithm, bucket_mb):
        def make(r):
            def per_rank(t):
                for _ in range(r):
                    t = bucketed_all_reduce(t, "dp", ReduceOp.AVG, algorithm, bucket_mb)
                return t

            return jax.jit(jax.shard_map(
                per_rank, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
            ))

        def p50_of(r):
            fn = make(r)
            out = fn(tree)
            float(out["w00"][0])  # compile + sync
            ts = []
            for _ in range(reps):
                t0 = time.monotonic()
                out = fn(out)
                float(out["w00"][0])
                ts.append((time.monotonic() - t0) * 1e3)
            return float(np.percentile(ts, 50))

        return max((p50_of(r_hi) - p50_of(1)) / (r_hi - 1), 0.0)

    algorithms = (
        ("ring", "q8_ring") if tiny
        else ("ring", "ring2", "q8", "q8_ring", "q8_ring2", "q4_ring", "q4_ring2")
    )
    sizes = ((4, "4mb"),) if tiny else ((None, "1buf"), (1, "1mb"), (4, "4mb"))
    rows: dict = {
        "payload_mb": round(total_bytes / (1 << 20), 1),
        "devices": 8,
        "tiny_grid": tiny,
    }
    for algorithm in algorithms:
        for bucket_mb, label in sizes:
            n_buckets = (
                1 if bucket_mb is None else plan_buckets(tree, bucket_mb).n_buckets
            )
            ms = per_sync_ms(algorithm, bucket_mb)
            rows[f"{algorithm}_{label}_ms"] = round(ms, 3)
            rows[f"{algorithm}_{label}_buckets"] = n_buckets

    # analytic wire bytes at the 4 MiB bucket size (per-bucket elements =
    # one 256 KiB leaf × 16 — every bucket is uniform here, so one bucket's
    # ratio is the grid's): the ≥2× acceptance row
    bucket_elems = total_elems // max(plan_buckets(tree, 4).n_buckets, 1)
    fp32_ring = ring_wire_bytes(bucket_elems, 8)
    for name, (scheme, bidir) in QUANT_RING_ALGORITHMS.items():
        qbytes = quantized_ring_wire_bytes(bucket_elems, 8, scheme, bidir)
        rows[f"{name}_wire_bytes_per_bucket"] = qbytes
        rows[f"{scheme}_{'ring2' if bidir else 'ring'}_wire_reduction"] = round(
            fp32_ring / qbytes, 2
        )
    rows["fp32_ring_wire_bytes_per_bucket"] = fp32_ring

    # ---- loss-trajectory parity: fp32 ring vs q8_ring+EF (the acceptance
    # leg), plus q8_ring no-EF and q8_ring2+EF on the full grid. int4 has
    # no parity leg by design: its ~0.5-quantum noise visibly perturbs the
    # trajectory (docs/TUNING.md states so) and a pass/fail row against the
    # q8 tolerance would just be red
    import optax

    from dsml_tpu.models.mlp import MLP
    from dsml_tpu.parallel.dp import make_dp_train_step
    from dsml_tpu.utils.data import synthetic_classification

    model = MLP(sizes=(64, 32, 4))
    data = synthetic_classification(512, 64, classes=4, seed=0)
    steps = 12 if tiny else 40
    bx, by = data.train_x, data.train_y

    def trajectory(algorithm, ef_on):
        opt = optax.sgd(0.05, momentum=0.9)
        step = make_dp_train_step(
            model.loss, opt, mesh, algorithm=algorithm, bucket_size_mb=4,
            error_feedback=ef_on,
        )
        params = model.init(0)
        opt_state = opt.init(params)
        ef = init_error_feedback(params, mesh, "dp") if ef_on else None
        out = []
        for s in range(steps):
            lo = (s * 64) % (len(bx) - 64)
            x, y = bx[lo:lo + 64], by[lo:lo + 64]
            if ef_on:
                params, opt_state, ef, loss = step(params, opt_state, ef, x, y)
            else:
                params, opt_state, loss = step(params, opt_state, x, y)
            out.append(float(loss))
        return out

    ref = trajectory("ring", False)
    # max per-step relative deviation vs the fp32 ring sync; over init
    # seeds 0-7 the 12-step q8+EF leg measures 0.004-0.053
    tolerance = 0.1

    def parity(tag, algorithm, ef_on):
        got = trajectory(algorithm, ef_on)
        rel_dev = max(
            abs(a - b) / max(abs(b), 1e-3) for a, b in zip(got, ref)
        )
        rows[f"parity_{tag}_final_loss"] = round(got[-1], 6)
        rows[f"parity_{tag}_rel_dev"] = round(rel_dev, 5)
        rows[f"parity_{tag}_ok"] = rel_dev <= tolerance

    rows["parity_fp32_final_loss"] = round(ref[-1], 6)
    rows["parity_steps"] = steps
    rows["parity_tolerance"] = tolerance
    parity("q8_ef", "q8_ring", True)
    if not tiny:
        parity("q8_noef", "q8_ring", False)
        parity("q8_ring2_ef", "q8_ring2", True)
    print(json.dumps(rows))


def bench_quant_sweep() -> dict:
    """The block-quantized collective grid (virtual-8 mesh subprocess, same
    pattern as :func:`bench_bucket_sweep`): per-sync ms + analytic wire
    bytes across (bucket size × quant scheme × ring/ring2), the
    ``*_wire_reduction`` rows the ≥2× acceptance bar reads, and the q8+EF
    loss-trajectory parity verdicts. The numbers the ``DSML_QUANT``
    per-dtype default is chosen from (docs/TUNING.md § Quantized
    collectives)."""
    code = "import bench; bench._quant_sweep_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=max(min(900.0, _budget_left()), 60.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "quant_sweep_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {f"quant_sweep_{k}": v for k, v in res.items()}
        out["quant_sweep_note"] = (
            "8-device virtual CPU mesh: _ms cells are relative signal (not "
            "ICI); wire_reduction rows are analytic byte counts; parity "
            "rows are measured loss trajectories vs the fp32 ring sync"
        )
        return out
    except Exception as e:  # never fail the bench on the secondary section
        return {"quant_sweep_error": repr(e)[:200]}


def bench_ring_virtual8() -> dict:
    """The same jitted ring program on an 8-device virtual CPU mesh — proof
    the 2(n−1)-hop harness measures a ring that actually hops. CPU
    collective timing, NOT ICI: labeled as such. Only worth
    running when the real-chip section couldn't hop (1 device)."""
    code = "import bench; bench._virtual8_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=".",
            # never overrun the global budget: the gate only guarantees
            # ~120s remained when this section started
            timeout=max(min(600.0, _budget_left()), 60.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "allreduce_virtual8_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        return {
            "allreduce_virtual8_ring_p50_ms": res["ring_ms"],
            "allreduce_virtual8_ring2_p50_ms": res.get("ring2_ms"),
            "allreduce_virtual8_naive_p50_ms": res["naive_ms"],
            "allreduce_virtual8_wire_e2e_p50_ms": res.get("wire_e2e_ms"),
            "allreduce_virtual8_note": "8-device virtual CPU mesh (harness proof, not ICI)",
        }
    except Exception as e:  # never fail the bench on the secondary section
        return {"allreduce_virtual8_error": repr(e)[:200]}


def _long_context_act_bytes(seq: int, cp: int, remat: str | bool,
                            n_layer: int = 12, d_model: int = 768,
                            d_ff: int = 3072, n_head: int = 12,
                            itemsize: int = 2) -> int:
    """Analytic per-chip ACTIVATION bytes of one GPT-2-small-shaped training
    forward at ``seq`` tokens sharded over ``cp`` ranks — the memory-headroom
    accounting the long_context section reports (exact counting over the
    saved-residual inventory, not a measurement).

    Per layer per resident token the backward must hold: the block input
    (d), the two LN outputs (2d), q/k/v (3d), the flash outputs out (d) +
    lse (one f32 PER HEAD — lse is [b, h, s]), the attention projection
    output (d), and — without selective remat — the MLP input (d) and
    hidden (ff). ``remat="mlp"`` drops the MLP pair (recomputed in
    backward; the selective mode ``models.gpt2`` implements);
    ``remat=True`` keeps only the block input. cp divides resident tokens
    by the ring size — THE headroom lever once a single chip's remat
    options are exhausted."""
    tokens = -(-seq // cp)
    if remat is True:
        per_tok_b = d_model * itemsize  # block input only; rest recomputes
    elif remat == "mlp":
        per_tok_b = 7 * d_model * itemsize + n_head * 4  # MLP pair dropped
    else:
        per_tok_b = (8 * d_model + d_ff) * itemsize + n_head * 4
    return n_layer * tokens * per_tok_b


def _long_context_main() -> None:
    """Subprocess entry: the sequence-length ladder PAST the single-chip
    32k ceiling — context-parallel ring attention (``attn_impl="ring2"``:
    bidirectional flash ring, causal hop skipping, KV re-streaming
    backward) on the cp=8 virtual CPU mesh, climbing 8k → 128k tokens in
    ONE sequence. CPU walls are relative signal (the Pallas kernels run
    interpreted); the structural claims — a 128k train step COMPLETES on
    8 ranks, per-hop KV wire bytes (exact counting), the activation
    headroom table, and fwd/bwd parity to single-device flash — carry the
    section. ``DSML_LONG_CONTEXT_TINY=1`` = the CI smoke ladder."""
    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.ops.ring_attention import causal_keep_fraction, ring_kv_wire_bytes
    from dsml_tpu.parallel.hybrid import init_hybrid, make_hybrid_train_step
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    tiny = os.environ.get("DSML_LONG_CONTEXT_TINY") == "1"
    cp = 8
    target = 131072
    rungs = [2048, 4096] if tiny else [8192, 16384, 32768, 65536, target]
    budget_s = float(os.environ.get(
        "DSML_LONG_CONTEXT_BUDGET_S", "120" if tiny else "2400"))
    t_start = time.monotonic()

    # attention-dominated harness model: 1 layer / d32 keeps the non-attention
    # tail tiny so the rung walls track the O(S²/cp) ring attention itself;
    # selective remat ("mlp") is the mode the headroom table argues for
    base = GPT2Config(
        vocab_size=256, max_seq=rungs[0], n_layer=1, n_head=2, d_model=32,
        d_ff=64, xent_chunk=0, remat="mlp", dtype="float32",
    )
    optimizer = optax.adam(1e-3)

    def run_step(seq: int, spec: MeshSpec, attn_impl: str | None, n_dev: int):
        cfg = _dc.replace(base, max_seq=seq)
        model = GPT2(cfg)
        mesh = build_mesh(spec, jax.devices()[:n_dev])
        params, opt_state = init_hybrid(model, optimizer, mesh)
        step = make_hybrid_train_step(model, optimizer, mesh, attn_impl=attn_impl)
        # per-rung seed: a budget-skipped rung must not shift later rungs'
        # tokens (and therefore their regress-gated final_loss rows)
        rng = np.random.default_rng(seq)
        x = jnp.asarray(rng.integers(0, 256, (1, seq)), jnp.int32)
        y = jnp.roll(x, -1, axis=1)
        t0 = time.monotonic()
        state = step(params, opt_state, x, y)
        loss = float(state[2])  # sync
        compile_s = time.monotonic() - t0
        t0 = time.monotonic()
        state = step(state[0], state[1], x, y)
        loss = float(state[2])
        step_s = time.monotonic() - t0
        return step_s, compile_s, loss

    rows: dict = {
        "devices": 8, "cp": cp, "batch": 1, "tiny": tiny,
        "model": "gpt2 L1 h2 d32 f32 remat=mlp (attention-dominated harness)",
        "ladder_target_tokens": target,
        "rungs_planned": rungs,
        "causal_keep_fraction_cp8": round(causal_keep_fraction(cp), 4),
    }

    # single-chip baseline at the FIRST rung (the largest both sides afford)
    s0 = rungs[0]
    single_tps = None
    try:
        step_s, compile_s, _ = run_step(s0, MeshSpec(dp=1), "flash", 1)
        single_tps = s0 / step_s
        rows["single_chip_seq"] = s0
        rows["single_chip_step_ms"] = round(step_s * 1e3, 1)
        rows["single_chip_tokens_per_sec"] = round(single_tps, 1)
    except Exception as e:
        rows["single_chip_error"] = repr(e)[:200]

    hd = base.d_model // base.n_head
    max_tokens = 0
    for seq in rungs:
        if time.monotonic() - t_start > budget_s:
            rows[f"seq{seq}_skipped"] = "ladder budget exhausted"
            continue
        # exact wire accounting is static — emit it even if the rung times out
        per_hop = ring_kv_wire_bytes(seq // cp, cp, base.n_head, hd) // (cp - 1)
        rows[f"seq{seq}_kv_wire_bytes_per_hop"] = per_hop
        rows[f"seq{seq}_kv_wire_bytes_fwd"] = ring_kv_wire_bytes(seq // cp, cp, base.n_head, hd)
        rows[f"seq{seq}_kv_wire_bytes_bwd"] = ring_kv_wire_bytes(
            seq // cp, cp, base.n_head, hd, backward=True)
        try:
            step_s, compile_s, loss = run_step(seq, MeshSpec(dp=1, cp=cp), None, 8)
        except Exception as e:
            rows[f"seq{seq}_error"] = repr(e)[:200]
            break
        max_tokens = seq
        rows[f"seq{seq}_step_ms"] = round(step_s * 1e3, 1)
        rows[f"seq{seq}_tokens_per_sec"] = round(seq / step_s, 1)
        rows[f"seq{seq}_compile_s"] = round(compile_s, 1)
        rows[f"seq{seq}_final_loss"] = round(loss, 3)
        if seq == s0 and single_tps:
            # same FLOPs per token at the same length, so the raw ratio is
            # the THROUGHPUT scaling; MFU normalizes by peak — the cp run
            # has cp× the aggregate peak, so the MFU ratio divides by cp.
            # (Virtual-8 caveat: the 8 "chips" share one host's cores, so
            # both rows are relative signal, not chip utilization.)
            ratio = (seq / step_s) / single_tps
            rows["throughput_vs_single_chip"] = round(ratio, 3)
            rows["mfu_vs_single_chip"] = round(ratio / cp, 4)
    rows["max_tokens"] = max_tokens

    # memory-headroom table: GPT-2-small shapes (bf16), the config the
    # single-chip 32k ceiling was measured on — what remat buys, then what
    # cp buys ON TOP once a chip's remat options are exhausted
    for seq in (32768, 65536, target):
        single = _long_context_act_bytes(seq, 1, False)
        single_remat = _long_context_act_bytes(seq, 1, "mlp")
        cp_remat = _long_context_act_bytes(seq, cp, "mlp")
        rows[f"gpt2s_{seq}_act_gb_single"] = round(single / 1e9, 2)
        rows[f"gpt2s_{seq}_act_gb_single_remat_mlp"] = round(single_remat / 1e9, 2)
        rows[f"gpt2s_{seq}_act_gb_cp8_remat_mlp"] = round(cp_remat / 1e9, 3)
    # GPT-2-small 128k wire headline: per-hop KV bytes each rank ships (bf16)
    rows["gpt2s_128k_kv_wire_mb_per_hop"] = round(
        ring_kv_wire_bytes(target // cp, cp, 12, 64, itemsize=2) / (cp - 1) / 1e6, 2)

    # parity leg: ring2 vs single-device flash on small shapes (odd length
    # included — the padded-kernel path), fwd AND grads
    from jax.sharding import Mesh, PartitionSpec as P

    from dsml_tpu.ops.attention import attention
    from dsml_tpu.ops.ring_attention import ring_attention

    rng = np.random.default_rng(1)
    fwd_err = grad_err = 0.0
    cases = 0
    for s, causal in ((256, True), (264, True), (256, False)):
        mesh = Mesh(np.asarray(jax.devices()[:cp]).reshape(cp), ("cp",))
        q, k, v = (jnp.asarray(rng.standard_normal((1, 2, s, 16)), jnp.float32)
                   for _ in range(3))
        spec = P(None, None, "cp", None)
        fn = jax.jit(jax.shard_map(
            lambda q, k, v, c=causal: ring_attention(q, k, v, "cp", c),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False))
        fwd_err = max(fwd_err, float(jnp.abs(fn(q, k, v) - attention(q, k, v, causal)).max()))
        g = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)
        r = jax.grad(lambda q, k, v, c=causal: jnp.sum(attention(q, k, v, c) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
        grad_err = max(grad_err, max(float(jnp.abs(a - b).max()) for a, b in zip(g, r)))
        cases += 1
    rows["parity_cases"] = cases
    rows["parity_fwd_max_err"] = fwd_err
    rows["parity_grad_max_err"] = grad_err
    rows["parity_ok"] = bool(fwd_err < 5e-4 and grad_err < 2e-3)
    print(json.dumps(rows))


def bench_long_context() -> dict:
    """Context-parallelism ladder rows (virtual-8 mesh subprocess, same
    pattern as :func:`bench_bucket_sweep`): the 8k→128k climb on the cp=8
    ring (``ops.ring_attention``), MFU-vs-single-chip at the shared rung,
    EXACT per-hop KV wire bytes, the remat+cp activation-headroom table,
    and ring-vs-flash parity verdicts. CPU walls are relative signal; the
    completion/wire/headroom/parity claims are the section's substance."""
    code = "import bench; bench._long_context_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=".",
            timeout=max(min(3000.0, _budget_left()), 180.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "long_context_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {f"long_context_{k}": v for k, v in res.items()}
        out["long_context_note"] = (
            "cp=8 virtual CPU mesh, Pallas kernels interpreted: rung walls "
            "are relative signal; completion, exact KV wire accounting, "
            "headroom table, and parity verdicts are the claims"
        )
        return out
    except Exception as e:  # never fail the bench on the secondary section
        return {"long_context_error": repr(e)[:200]}


def _memory_main() -> None:
    """Subprocess entry for :func:`bench_memory` (virtual-8 CPU mesh):
    the memory-ledger section (docs/OBSERVABILITY.md § Memory ledger).

    (a) attribution: a dp=8 hybrid init's ledger claims pinned against
        hand-counted per-device tree bytes, plus per-step peak watermarks
        recorded by the wrapped hybrid step;
    (b) reconciliation: ledger-claimed vs ``memory_stats``-measured bytes
        within the documented bound on backends that report stats, and an
        injected-stats self-check (exact residual math) everywhere;
    (c) the analytic long-context headroom table cross-checked against
        COMPILER-measured per-rung temp bytes (``memory_analysis`` of the
        compiled step — compile-only, no execution) on the same harness
        shapes the long_context ladder uses;
    (d) disabled-mode ledger overhead vs a fused step (< 1% bar);
    (e) an injected RESOURCE_EXHAUSTED produces a postmortem bundle whose
        ``memory.json`` carries the ledger snapshot + watermark timeline;
    (f) the fleet merge: two processes' ledger gauges →
        ``MergedView.report()['memory']`` headroom min/mean/max.

    ``DSML_MEMORY_TINY=1`` trims the rung ladder for CI smoke.
    """
    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    import dataclasses as _dc
    import shutil
    import tempfile

    import jax
    import numpy as np
    import optax

    from dsml_tpu import obs
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.obs import cluster as obs_cluster
    from dsml_tpu.obs import memory as obs_memory
    from dsml_tpu.parallel.auto import measured_activation_bytes
    from dsml_tpu.parallel.hybrid import init_hybrid, make_hybrid_train_step
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    tiny = os.environ.get("DSML_MEMORY_TINY") == "1"
    rows: dict = {"devices": 8, "tiny": tiny}
    reg = obs.get_registry()
    reg.enable()
    led = obs_memory.get_memory_ledger()
    led.clear()

    # (a) attribution math + step watermarks: dp=8 hybrid on the tiny GPT-2
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    optimizer = optax.adam(1e-3)
    mesh = build_mesh(MeshSpec(dp=8), jax.devices()[:8])
    params, opt_state = init_hybrid(model, optimizer, mesh)
    # hand-count INDEPENDENTLY of tree_nbytes (plain shape arithmetic —
    # on the dp-only mesh every leaf is replicated, so per-device bytes
    # must equal the logical total; a shard-accounting bug in the ledger
    # cannot cancel against itself here)
    import math as _math

    def hand_count(tree):
        return sum(
            _math.prod(l.shape) * np.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(tree) if hasattr(l, "shape")
        )

    hand_params = hand_count(params)
    hand_opt = hand_count(opt_state)
    claims = led.claimed()
    rows["claimed_params_bytes"] = claims.get("params", {}).get("hybrid")
    rows["claimed_optimizer_bytes"] = claims.get("optimizer", {}).get("hybrid")
    rows["attribution_params_ok"] = int(
        claims.get("params", {}).get("hybrid") == hand_params)
    rows["attribution_optimizer_ok"] = int(
        claims.get("optimizer", {}).get("hybrid") == hand_opt)
    step = make_hybrid_train_step(model, optimizer, mesh)
    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.vocab_size, (8, cfg.max_seq)).astype(np.int32)
    y = np.roll(x, -1, 1).astype(np.int32)
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, x, y)
    float(loss)
    marks = led.watermarks()
    rows["step_watermarks"] = len(marks)
    rows["step_peak_bytes"] = marks[-1]["peak_bytes"] if marks else None
    rows["watermark_source"] = marks[-1]["source"] if marks else "none"

    # (b) reconciliation: measured when the backend reports stats, and an
    # injected-stats self-check whose residual math is exact everywhere
    bound_pct = 10.0  # documented bound (docs § Memory ledger)
    m = led.measure()
    rows["stats_available"] = int(m["available"])
    rows["reconcile_bound_pct"] = bound_pct
    if m["available"]:
        resid_pct = (abs(led.unattributed_bytes())
                     / max(m["bytes_in_use"], 1) * 100.0)
        rows["reconcile_residual_pct"] = round(resid_pct, 3)
        rows["reconcile_ok"] = int(resid_pct <= bound_pct)
        rows["hbm_bytes_limit"] = m["bytes_limit"]
    claimed_total = led.claimed_bytes()
    fake = [{"device": "synthetic", "bytes_in_use": int(claimed_total * 1.03),
             "peak_bytes_in_use": int(claimed_total * 1.10),
             "bytes_limit": int(claimed_total * 4)}]
    sreg = obs.Registry(enabled=True)
    sled = obs_memory.MemoryLedger(registry=sreg, stats_fn=lambda: fake)
    sled.set_claim("params", claimed_total)
    expected = int(claimed_total * 1.03) - claimed_total
    resid = sled.unattributed_bytes()
    rows["selfcheck_expected_residual_bytes"] = expected
    rows["selfcheck_residual_bytes"] = resid
    rows["selfcheck_ok"] = int(abs(resid - expected) < 1.0)

    # (c) analytic headroom table vs compiler-measured per-rung temps on
    # the long_context harness shapes (L1 h2 d32 f32 remat=mlp) — the
    # measured column the 128k table's analytic rows are cross-checked
    # against (compile-only: memory_analysis of the lowered step)
    rungs = [1024, 2048] if tiny else [2048, 4096, 8192]
    base = GPT2Config(
        vocab_size=256, max_seq=rungs[0], n_layer=1, n_head=2, d_model=32,
        d_ff=64, xent_chunk=0, remat="mlp", dtype="float32",
    )
    measured_by_rung: dict = {}
    for seq in rungs:
        mcfg = _dc.replace(base, max_seq=seq)
        mmodel = GPT2(mcfg)
        mparams = mmodel.init(0)

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        xs = jax.ShapeDtypeStruct((1, seq), np.int32)
        measured = measured_activation_bytes(
            mmodel.loss, jax.tree.map(sds, mparams), xs, xs)
        analytic = _long_context_act_bytes(
            seq, 1, "mlp", n_layer=1, d_model=32, d_ff=64, n_head=2,
            itemsize=4)
        rows[f"rung{seq}_analytic_act_bytes"] = analytic
        if measured is None:
            rows[f"rung{seq}_measured_error"] = "no memory_analysis"
            continue
        measured_by_rung[seq] = measured
        rows[f"rung{seq}_measured_temp_bytes"] = int(measured)
        rows[f"rung{seq}_measured_over_analytic"] = round(measured / analytic, 2)
    if len(measured_by_rung) >= 2:
        seqs = sorted(measured_by_rung)
        # the structural claim: measured temps GROW with the rung (the
        # exact slope is the compiler's business — CPU fusion keeps
        # attention temps O(S²), the analytic rows count saved residuals)
        rows["rung_monotonic_ok"] = int(all(
            measured_by_rung[a] < measured_by_rung[b]
            for a, b in zip(seqs, seqs[1:])
        ))
        rows["rung_measured_per_token_bytes"] = round(
            measured_by_rung[seqs[-1]] / seqs[-1], 1)

    # (d) disabled-mode overhead: the exact per-step ledger bundle the
    # wired hot paths run when obs is off (one watermark + one claim, both
    # early-returning) vs a fused train step — the <1% bar
    d = 256
    import jax.numpy as jnp

    mlp_params = {
        f"p{i}": jnp.asarray(rng.standard_normal((d, d)), jnp.float32)
        for i in range(4)
    }
    mlp_opt = optax.adam(1e-3)
    mlp_state = mlp_opt.init(mlp_params)
    xb = jnp.asarray(rng.standard_normal((64, d)).astype(np.float32))

    def mlp_loss(p, xb):
        h = xb
        for i in range(4):
            h = jnp.tanh(h @ p[f"p{i}"])
        return jnp.mean(h * h)

    def fused(p, o, xb):
        loss, g = jax.value_and_grad(mlp_loss)(p, xb)
        up, o = mlp_opt.update(g, o, p)
        return optax.apply_updates(p, up), o, loss

    fused_fn = jax.jit(fused)
    p0, o0, loss = fused_fn(mlp_params, mlp_state, xb)
    float(loss)

    def step_wall(k: int = 40) -> float:
        pp, oo = p0, o0
        t0 = time.perf_counter()
        for _ in range(k):
            pp, oo, ls = fused_fn(pp, oo, xb)
        float(ls)
        return (time.perf_counter() - t0) / k

    step_s = min(step_wall() for _ in range(3))
    reg_off = obs.Registry(enabled=False)
    led_off = obs_memory.MemoryLedger(registry=reg_off)
    n_iter = 100_000
    t0 = time.perf_counter()
    for i in range(n_iter):
        led_off.note_step_peak(i)
        led_off.set_claim("params", 1.0)
    bundle_s = (time.perf_counter() - t0) / n_iter
    rows["disabled_bundle_ns"] = round(bundle_s * 1e9, 1)
    rows["disabled_overhead_pct"] = round(100.0 * bundle_s / step_s, 4)
    rows["fused_step_wall_ms"] = round(step_s * 1e3, 3)

    # (e) injected OOM → postmortem bundle with the ledger snapshot
    tmp = tempfile.mkdtemp(prefix="dsml_memory_bench_")
    try:
        rec = obs.FlightRecorder(registry=reg, directory=tmp)
        exc = RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 1073741824 bytes")
        bundle = obs_memory.maybe_dump_oom(exc, recorder=rec)
        with open(os.path.join(bundle, "MANIFEST.json")) as f:
            manifest = json.load(f)
        with open(os.path.join(bundle, "memory.json")) as f:
            mem_snap = json.load(f)
        rows["memory_oom_bundle_files"] = manifest["files"]
        rows["memory_oom_reason_ok"] = int("resource_exhausted" in bundle)
        rows["memory_oom_snapshot_ok"] = int(
            mem_snap.get("schema") == obs_memory.SCHEMA
            and mem_snap.get("claimed_total_bytes", 0) > 0
        )
        rows["memory_oom_watermarks"] = len(mem_snap.get("watermarks", []))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # (f) fleet merge of the ledger gauges: two synthetic hosts' ledgers
    # (injected stats at different headroom) → report()['memory']
    fleet_rows = {}
    keep = []  # the ledgers must outlive their registries' collect
    for i, (use, limit) in enumerate(((6e9, 16e9), (11e9, 16e9))):
        freg = obs.Registry(enabled=True)
        fled = obs_memory.MemoryLedger(
            registry=freg,
            stats_fn=(lambda u=use, li=limit: [{
                "device": "synthetic", "bytes_in_use": int(u),
                "peak_bytes_in_use": int(u), "bytes_limit": int(li),
            }]),
        )
        fled.set_claim("params", use * 0.9)
        keep.append(fled)
        snap = obs_cluster.snapshot(role=f"worker{i}", registry=freg,
                                    with_trace=False)
        fleet_rows[i] = snap
    merged = obs_cluster.merge_snapshots(list(fleet_rows.values()))
    memory_report = merged.report()["memory"]
    head = memory_report.get("headroom_bytes", {})
    rows["fleet_headroom_min_gb"] = round(head.get("min", 0) / 1e9, 2)
    rows["fleet_headroom_mean_gb"] = round(head.get("mean", 0) / 1e9, 2)
    rows["fleet_headroom_max_gb"] = round(head.get("max", 0) / 1e9, 2)
    rows["fleet_headroom_ok"] = int(
        bool(head) and head["min"] <= head["mean"] <= head["max"]
        and head["n"] == 2
    )
    rows["fleet_unattributed_rows"] = memory_report.get(
        "unattributed_bytes", {}).get("n", 0)
    print(json.dumps(rows))


def bench_memory() -> dict:
    """Memory-ledger section (virtual-8 mesh subprocess, same pattern as
    :func:`bench_bucket_sweep`): ledger-vs-measured reconciliation with
    the documented bound, the analytic-vs-compiler-measured rung
    cross-check, the disabled-overhead bar, the injected-OOM postmortem
    bundle, and the fleet merge of ledger gauges. CPU meshes report no
    ``memory_stats`` — the claimed/compiler columns carry the section
    there, and the live-reconciliation row lights up on TPU."""
    code = "import bench; bench._memory_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=".",
            timeout=max(min(600.0, _budget_left()), 120.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "memory_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {
            (k if k.startswith("memory_") else f"memory_{k}"): v
            for k, v in res.items()
        }
        out["memory_note"] = (
            "virtual-8 CPU mesh: attribution/self-check/OOM/fleet rows are "
            "exact; memory_stats reconciliation requires a stats-reporting "
            "backend (TPU) — provenance is carried, never guessed"
        )
        return out
    except Exception as e:  # never fail the bench on the secondary section
        return {"memory_error": repr(e)[:200]}


def bench_mnist() -> dict:
    """The reference's own workload (MNIST MLP ladder config #1) as a fully
    device-resident program: dataset in HBM, each epoch ONE jitted
    ``lax.scan`` over SGD steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dsml_tpu.models.mlp import MLP
    from dsml_tpu.utils.data import load_mnist

    batch = 256
    epochs_timed = 3
    lr = 0.1

    data = load_mnist()
    n = (data.n_train // batch) * batch
    steps = n // batch

    dev = jax.devices()[0]
    x_dev = jax.device_put(jnp.asarray(data.train_x[:n]), dev)
    y_dev = jax.device_put(jnp.asarray(data.train_y[:n]), dev)

    model = MLP()
    optimizer = optax.sgd(lr, momentum=0.9)
    params = jax.device_put(model.init(0), dev)
    opt_state = jax.device_put(optimizer.init(params), dev)

    def make_run(n_epochs: int):
        @jax.jit
        def run(params, opt_state, perms):  # perms [n_epochs, steps, batch]
            def body(carry, idx):
                params, opt_state = carry
                loss, grads = jax.value_and_grad(model.loss)(params, x_dev[idx], y_dev[idx])
                updates, opt_state = optimizer.update(grads, opt_state, params)
                return (optax.apply_updates(params, updates), opt_state), loss

            def epoch(carry, perm):
                carry, losses = jax.lax.scan(body, carry, perm)
                return carry, losses.mean()

            (params, opt_state), losses = jax.lax.scan(epoch, (params, opt_state), perms)
            return params, opt_state, losses[-1]

        return run

    rng = np.random.default_rng(0)

    def perms_for(n_epochs: int):
        idx = np.stack(
            [rng.permutation(n).astype(np.int32)[: steps * batch] for _ in range(n_epochs)]
        )
        return jnp.asarray(idx.reshape(n_epochs, steps, batch))

    # All epochs of one measurement run inside ONE jitted program; timing
    # R=1 vs R=1+epochs_timed and differencing cancels the per-dispatch
    # overhead.
    run1, runN = make_run(1), make_run(1 + epochs_timed)

    t0 = time.monotonic()
    params, opt_state, loss = run1(params, opt_state, perms_for(1))
    float(loss)
    params, opt_state, loss = runN(params, opt_state, perms_for(1 + epochs_timed))
    float(loss)
    compile_s = time.monotonic() - t0

    def p50(fn, n_epochs, reps=5):
        perms = perms_for(n_epochs)  # host RNG + H2D stay OUT of the timing
        ts = []
        for _ in range(reps):
            t0 = time.monotonic()
            p, o, loss = fn(params, opt_state, perms)
            float(loss)
            ts.append(time.monotonic() - t0)
        return float(np.percentile(ts, 50)), (p, o, loss)

    tN, _ = p50(runN, 1 + epochs_timed)
    t1, (params, opt_state, loss) = p50(run1, 1)
    if tN - t1 > 1e-3:
        wall = tN - t1
        timing_mode = "differenced"  # dispatch overhead cancelled
    else:
        # jitter swamped the difference; fall back to the absolute (1+E)-epoch
        # time — conservative (includes one dispatch), never absurd
        wall = tN * epochs_timed / (1 + epochs_timed)
        timing_mode = "absolute"
    samples_per_sec = epochs_timed * steps * batch / wall

    # quick accuracy check with the trained params (not part of the timing)
    test_acc = float(
        jnp.mean(jnp.argmax(model.apply(params, jnp.asarray(data.test_x)), -1) == jnp.asarray(data.test_y))
    )

    out = {
        "mnist_samples_per_sec": round(samples_per_sec, 1),
        "mnist_batch": batch,
        "mnist_epochs_timed": epochs_timed,
        "mnist_steps_per_epoch": steps,
        "mnist_compile_s": round(compile_s, 2),
        "mnist_timed_wall_s": round(wall, 3),
        "mnist_timing_mode": timing_mode,
        "mnist_final_train_loss": round(float(loss), 4),
        "mnist_test_accuracy": round(test_acc, 4),
        "reference_samples_per_sec": REFERENCE_SAMPLES_PER_SEC,
        # NOT emitted as a vs_baseline ratio: the data protocol differs from
        # the reference's 60k/10k (see data_provenance), and a ~100K-param MLP
        # epoch is sub-ms on a TPU — the ratio carries no information
        "mnist_note": (
            "a 101k-param MLP is fully HBM-resident, so the differenced "
            "per-step time (~us) measures XLA scan-loop overhead, not "
            "meaningful compute throughput — samples/s varies run-to-run "
            "accordingly and is a ceiling demonstration; test_accuracy is "
            "the signal (reference: 92.89%), and the flagship GPT-2 rows "
            "are where throughput claims live"
        ),
    }
    # accuracy headline: the CNN banks margin over the >97% BASELINE target
    # that the MLP saturates under (fallback split). Same device-resident
    # all-epochs-in-one-program shape as the MLP ladder above. Accelerator
    # only: CPU conv over the 40k augmented rows costs ~10 min for a row
    # that would carry no TPU signal anyway
    if dev.platform == "cpu":
        out["mnist_cnn_skipped"] = (
            "CPU backend: the CNN accuracy row is captured on the real chip"
        )
    elif not _skip_for_budget(out, "mnist_cnn", 240):
        try:
            from dsml_tpu.models.cnn import CNN

            cnn = CNN()
            cnn_epochs = 12
            copt = optax.adamw(1e-3)
            cparams = jax.device_put(cnn.init(0), dev)
            cstate = jax.device_put(copt.init(cparams), dev)

            @jax.jit
            def run_cnn(p, o, perms):
                def body(carry, idx):
                    p, o = carry
                    loss, g = jax.value_and_grad(cnn.loss)(p, x_dev[idx], y_dev[idx])
                    up, o = copt.update(g, o, p)
                    return (optax.apply_updates(p, up), o), loss

                def epoch(carry, perm):
                    carry, losses = jax.lax.scan(body, carry, perm)
                    return carry, losses.mean()

                (p, o), losses = jax.lax.scan(epoch, (p, o), perms)
                return p, o, losses[-1]

            t0 = time.monotonic()
            cparams, cstate, closs = run_cnn(cparams, cstate, perms_for(cnn_epochs))
            closs = float(closs)
            cnn_wall = time.monotonic() - t0
            cnn_acc = float(jnp.mean(
                jnp.argmax(cnn.apply(cparams, jnp.asarray(data.test_x)), -1)
                == jnp.asarray(data.test_y)
            ))
            out.update({
                "mnist_cnn_test_accuracy": round(cnn_acc, 4),
                "mnist_cnn_epochs": cnn_epochs,
                "mnist_cnn_params": int(sum(
                    v.size for v in jax.tree.leaves(cparams))),
                "mnist_cnn_final_train_loss": round(closs, 4),
                "mnist_cnn_compile_and_train_s": round(cnn_wall, 1),
                "mnist_cnn_note": (
                    "accuracy headline on the fallback split (same "
                    "augmented 8k/2k protocol label as the MLP rows); "
                    "reference bar 92.89% on its 60k/10k protocol"
                ),
            })
            # only claim the CNN headline when the row actually landed —
            # a skipped/errored CNN must not leave the note pointing at a
            # key the artifact doesn't carry
            out["mnist_note"] += (
                "; mnist_cnn_test_accuracy is the accuracy HEADLINE (the "
                "MLP saturates the fallback split around ~97.5%)"
            )
        except Exception as e:
            out["mnist_cnn_error"] = repr(e)[:200]
    return out


def bench_checkpoint() -> dict:
    """Save-path cost of the native checkpoint subsystem
    (``dsml_tpu/checkpoint/``): sync save/restore wall time for a
    train-state-shaped pytree, and — the number that matters for the step
    loop — how much of one step an ASYNC save actually stalls (the
    device→host snapshot is the only synchronous part; the disk commit
    rides a background thread). Acceptance bar from the subsystem's issue:
    async stall < 10% of one step time."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dsml_tpu.checkpoint import CheckpointManager

    # sized so the step is representative of a real training step relative
    # to its state (the stall-pct metric is workload-relative: a toy step
    # under a full-sized state would "fail" any async writer)
    d = int(_env_float("DSML_CKPT_BENCH_D", 768))
    batch = int(_env_float("DSML_CKPT_BENCH_BATCH", 4096))
    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    params = {
        f"w{i}": jax.device_put(
            jnp.asarray(rng.standard_normal((d, d)).astype(np.float32)), dev
        )
        for i in range(4)
    }
    optimizer = optax.adam(1e-3)
    opt_state = jax.device_put(optimizer.init(params), dev)
    x = jax.device_put(jnp.asarray(rng.standard_normal((batch, d)).astype(np.float32)), dev)
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves((params, opt_state)))

    def loss_fn(p, x):
        h = x
        for i in range(4):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean(h * h)

    @jax.jit
    def step(p, o, x):
        loss, g = jax.value_and_grad(loss_fn)(p, x)
        up, o = optimizer.update(g, o, p)
        return optax.apply_updates(p, up), o, loss

    params, opt_state, loss = step(params, opt_state, x)  # compile
    float(loss)

    def timed_steps(k: int) -> float:
        t0 = time.monotonic()
        nonlocal params, opt_state
        for _ in range(k):
            params, opt_state, loss = step(params, opt_state, x)
        float(loss)  # one sync at the end
        return (time.monotonic() - t0) / k

    baseline_step_ms = 1e3 * float(np.percentile([timed_steps(8) for _ in range(3)], 50))

    tmp = tempfile.mkdtemp(prefix="dsml_ckpt_bench_")
    try:
        mgr = CheckpointManager(tmp, max_to_keep=2)
        # sync save / restore
        saves, restores = [], []
        for rep in range(3):
            t0 = time.monotonic()
            mgr.save(rep, {"params": params, "opt_state": opt_state})
            saves.append(time.monotonic() - t0)
            t0 = time.monotonic()
            mgr.restore(rep, template={"params": params, "opt_state": opt_state})
            restores.append(time.monotonic() - t0)
        # async: the step loop pays ONLY the save() call (snapshot+enqueue)
        # plus whatever the background write steals from the next steps
        stall_calls, loops = [], []
        for rep in range(3):
            t0 = time.monotonic()
            mgr.save(100 + rep, {"params": params, "opt_state": opt_state},
                     wait=False)
            stall_calls.append(time.monotonic() - t0)
            loops.append(timed_steps(8))
            mgr.wait_until_finished()
        mgr.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    sched_ms = 1e3 * float(np.percentile(stall_calls, 50))
    during_ms = 1e3 * float(np.percentile(loops, 50))
    # per-step inflation while the write is in flight (clamped at 0: noise)
    inflation_ms = max(0.0, during_ms - baseline_step_ms)
    stall_ms = sched_ms + inflation_ms
    return {
        "checkpoint_state_mb": round(state_bytes / 2**20, 1),
        "checkpoint_save_ms": round(1e3 * float(np.percentile(saves, 50)), 2),
        "checkpoint_restore_ms": round(1e3 * float(np.percentile(restores, 50)), 2),
        "checkpoint_async_schedule_ms": round(sched_ms, 2),
        "checkpoint_async_step_inflation_ms": round(inflation_ms, 3),
        "checkpoint_async_stall_ms": round(stall_ms, 2),
        "checkpoint_step_ms": round(baseline_step_ms, 2),
        "checkpoint_async_stall_pct_of_step": round(100 * stall_ms / max(baseline_step_ms, 1e-9), 1),
        "checkpoint_note": (
            "native sharded backend (docs/CHECKPOINT.md); async stall = "
            "save() call (host snapshot + enqueue) + p50 per-step inflation "
            "while the background commit is in flight — the <10%-of-a-step "
            "acceptance metric"
        ),
    }


def bench_obs() -> dict:
    """Observability-subsystem section (``docs/OBSERVABILITY.md``), three
    sub-rows on whatever mesh is local (backend-agnostic; CPU rows carry
    structural signal — schema + coverage — not TPU latency):

    (a) per-algorithm collective-latency HISTOGRAMS through the registry
        (``collective_latency_ms{algorithm,axis}``) — the EQuARX-style
        accounting the q8 path needs;
    (b) a PHASED step breakdown (data / forward_backward / grad_sync /
        optimizer / checkpoint_stall), each phase its own fenced program,
        whose components must sum to within 5% of the measured step wall
        (``obs_step_coverage_pct`` >= 95 is the acceptance bar);
    (c) the zero-overhead guard: the same fused step loop with
        disabled-registry instrumentation vs bare, alternating reps —
        ``obs_disabled_overhead_pct`` must stay under 1.
    """
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from dsml_tpu import obs
    from dsml_tpu.checkpoint import CheckpointManager
    from dsml_tpu.ops.collectives import ReduceOp
    from dsml_tpu.parallel.bucketing import bucketed_all_reduce
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    reg = obs.get_registry()
    was_enabled = reg.enabled
    reg.enable()
    out: dict = {}
    try:
        devs = jax.devices()
        n = len(devs)
        mesh = build_mesh(MeshSpec(dp=n), devs)
        rng = np.random.default_rng(0)
        # 8 × 128 KiB f32 leaves (1 MiB): small enough to stay cheap on the
        # CPU mesh, large enough that 0.25 MiB buckets give a real count
        tree = {
            f"w{i}": jnp.asarray(rng.standard_normal(32_768), jnp.float32)
            for i in range(8)
        }
        payload = sum(l.size * 4 for l in jax.tree.leaves(tree))
        lat_hist = reg.histogram(
            "collective_latency_ms", "measured all-reduce latency",
            labels=("algorithm", "axis"),
        )
        reps = 8
        algorithms = ("ring", "ring2", "naive", "q8")
        for algorithm in algorithms:
            try:
                fn = jax.jit(jax.shard_map(
                    lambda t, alg=algorithm: bucketed_all_reduce(
                        t, "dp", ReduceOp.AVG, alg, 0.25
                    ),
                    mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
                ))
                r = fn(tree)
                float(r["w0"][0])  # compile + sync
                for _ in range(reps):
                    t0 = time.perf_counter()
                    r = fn(r)
                    float(r["w0"][0])
                    obs.observe_collective_latency_ms(
                        algorithm, (time.perf_counter() - t0) * 1e3,
                        payload_bytes=payload,
                    )
                s = lat_hist.summary(algorithm=algorithm, axis="dp")
                out[f"obs_collective_{algorithm}_p50_ms"] = round(s["p50"], 3)
                out[f"obs_collective_{algorithm}_p90_ms"] = round(s["p90"], 3)
                out[f"obs_collective_{algorithm}_n"] = s["count"]
            except Exception as e:
                out[f"obs_collective_{algorithm}_error"] = repr(e)[:200]
        # the full cumulative histograms (Prometheus bucket shape) for the
        # artifact — per-algorithm latency distribution, not just p50/p90
        out["obs_collective_latency_hist"] = {
            rec["labels"]["algorithm"]: rec["buckets"]
            for rec in reg.collect()
            if rec["name"] == "collective_latency_ms"
            and rec["labels"].get("axis") == "dp"
        }
        out["obs_collective_payload_bytes"] = payload
        out["obs_devices"] = n

        # (b) phased step breakdown: each phase its own jitted program with
        # an explicit fence, so the components are honestly separable (the
        # production fused step is ONE program — this decomposition is what
        # the obs subsystem exists to measure when asked)
        d, batch = 256, 64 * n
        params = {
            f"p{i}": jnp.asarray(rng.standard_normal((d, d)), jnp.float32)
            for i in range(4)
        }
        optimizer = optax.adam(1e-3)
        opt_state = optimizer.init(params)
        x_host = rng.standard_normal((batch, d)).astype(np.float32)

        def loss_fn(p, xb):
            h = xb
            for i in range(4):
                h = jnp.tanh(h @ p[f"p{i}"])
            return jnp.mean(h * h)

        grads_fn = jax.jit(lambda p, xb: jax.value_and_grad(loss_fn)(p, xb))
        sync_fn = jax.jit(jax.shard_map(
            lambda g: bucketed_all_reduce(g, "dp", ReduceOp.AVG, "ring", 0.25),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
        ))

        def opt_step(p, o, g):
            up, o = optimizer.update(g, o, p)
            return optax.apply_updates(p, up), o

        opt_fn = jax.jit(opt_step)
        # warm every program outside the timed loop
        loss, grads = grads_fn(params, jnp.asarray(x_host))
        float(loss)
        grads = sync_fn(grads)
        wp, wo = opt_fn(params, opt_state, grads)
        float(wp["p0"][0, 0])

        bd = obs.StepBreakdown(registry=reg)
        tmp = tempfile.mkdtemp(prefix="dsml_obs_bench_")
        try:
            mgr = CheckpointManager(tmp, max_to_keep=2)
            n_steps = 12
            for k in range(n_steps):
                with bd.step():
                    with bd.phase("data"):
                        xb = jnp.asarray(np.roll(x_host, k, axis=0))
                    with bd.phase("forward_backward"):
                        loss, grads = grads_fn(params, xb)
                        float(loss)
                    with bd.phase("grad_sync"):
                        grads = sync_fn(grads)
                        float(grads["p0"][0, 0])
                    with bd.phase("optimizer"):
                        params, opt_state = opt_fn(params, opt_state, grads)
                        float(params["p0"][0, 0])
                    if k % 4 == 0:
                        with bd.phase("checkpoint_stall"):
                            mgr.save(k, {"params": params}, wait=False)
            mgr.close()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        summary = bd.summary()
        out["obs_step_breakdown_ms"] = {
            name: info["mean_ms"] for name, info in summary["phases"].items()
        }
        out["obs_step_wall_ms"] = summary["step_wall_mean_ms"]
        # the acceptance bar: phases sum to within 5% of measured wall
        out["obs_step_coverage_pct"] = summary["coverage_pct"]

        # (c) disabled-overhead guard: one fused jitted step per iteration,
        # instrumented exactly like the wired hot paths are when the
        # registry is DISABLED (one enabled check + no-op counter/histogram
        # writes) vs entirely bare. Alternating reps + median difference so
        # scheduler jitter can't manufacture a regression.
        reg_off = obs.Registry(enabled=False)
        guard_c = reg_off.counter("obs_guard_total")
        guard_h = reg_off.histogram("obs_guard_ms")

        def fused(p, o, xb):
            loss, g = jax.value_and_grad(loss_fn)(p, xb)
            up, o = optimizer.update(g, o, p)
            return optax.apply_updates(p, up), o, loss

        fused_fn = jax.jit(fused)
        xb = jnp.asarray(x_host)
        p, o, loss = fused_fn(params, opt_state, xb)
        float(loss)

        # per-step cost of DISABLED instrumentation, measured directly: a
        # tight loop over exactly the per-step bundle the wired hot paths
        # run when the registry is off (one `if enabled:` gate + unguarded
        # inc()/observe() early-returns). A/B wall-differencing two ~ms
        # step loops cannot resolve a sub-µs cost against this host's
        # scheduler noise; cost-per-bundle ÷ step-time can.
        track = reg_off.enabled  # False
        n_bundles = 100_000
        t0 = time.perf_counter()
        for _ in range(n_bundles):
            if track:  # the trainer's `if track:` gate
                pass
            guard_c.inc()
            guard_h.observe(0.0)
        bundle_s = (time.perf_counter() - t0) / n_bundles

        def step_wall(k: int = 40) -> float:
            pp, oo = p, o
            t0 = time.perf_counter()
            for _ in range(k):
                pp, oo, ls = fused_fn(pp, oo, xb)
            float(ls)
            return (time.perf_counter() - t0) / k

        step_s = min(step_wall() for _ in range(3))
        out["obs_disabled_bundle_ns"] = round(bundle_s * 1e9, 1)
        out["obs_disabled_overhead_pct"] = round(100.0 * bundle_s / step_s, 4)
        out["obs_note"] = (
            "collective latencies are per-algorithm registry histograms "
            "(CPU meshes: relative signal, not ICI); step breakdown phases "
            "are separately-fenced programs and must cover >=95% of wall; "
            "disabled-registry instrumentation must cost <1% of a fused step"
        )
    finally:
        if not was_enabled:
            reg.disable()
    return out


def bench_forensics() -> dict:
    """Failure-forensics section (``docs/OBSERVABILITY.md`` § Failure
    forensics), three sub-rows on private obs instances:

    (a) DISABLED overhead guard: the per-step forensic bundle exactly as
        the trainer wires it when nothing is configured (sentinel branch
        + hangwatch branch + flight-recorder record on a disabled
        registry) — cost ÷ fused-step wall must stay under the existing
        <1% bar (``forensics_disabled_overhead_pct``);
    (b) ENABLED per-step overhead: the same bundle live (sentinel check +
        ring append + hangwatch arm/disarm) — also < 1% of a fused step
        (``forensics_enabled_overhead_pct``);
    (c) injected-NaN detection latency: the batch goes NaN at step k; a
        halt-policy sentinel checked at the trainer's sync cadence must
        trip at the next sync point, leaving a postmortem bundle whose
        event/file inventory the row reports.
    """
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dsml_tpu import obs
    from dsml_tpu.obs.sentinels import SentinelConfig, SentinelTripped, TrainingSentinels

    out: dict = {}
    rng = np.random.default_rng(0)
    d, batch = 256, 64
    params = {
        f"p{i}": jnp.asarray(rng.standard_normal((d, d)), jnp.float32)
        for i in range(4)
    }
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    x_host = rng.standard_normal((batch, d)).astype(np.float32)

    def loss_fn(p, xb):
        h = xb
        for i in range(4):
            h = jnp.tanh(h @ p[f"p{i}"])
        return jnp.mean(h * h)

    def fused(p, o, xb):
        loss, g = jax.value_and_grad(loss_fn)(p, xb)
        up, o = optimizer.update(g, o, p)
        return optax.apply_updates(p, up), o, loss

    fused_fn = jax.jit(fused)
    xb = jnp.asarray(x_host)
    p0, o0, loss = fused_fn(params, opt_state, xb)
    float(loss)

    def step_wall(k: int = 40) -> float:
        pp, oo = p0, o0
        t0 = time.perf_counter()
        for _ in range(k):
            pp, oo, ls = fused_fn(pp, oo, xb)
        float(ls)
        return (time.perf_counter() - t0) / k

    step_s = min(step_wall() for _ in range(3))

    # (a) disabled bundle: exactly the trainer's per-batch forensic cost
    # when DSML_SENTINELS/DSML_HANGWATCH are unset and the registry is off
    reg_off = obs.Registry(enabled=False)
    rec_off = obs.FlightRecorder(registry=reg_off)
    sentinels_off = None
    hw_off = None
    n_iter = 100_000
    t0 = time.perf_counter()
    for i in range(n_iter):
        if hw_off is not None:
            pass
        rec_off.record("step", step=i, wall_ms=0.0)
        if sentinels_off is not None:
            pass
    disabled_s = (time.perf_counter() - t0) / n_iter
    out["forensics_disabled_bundle_ns"] = round(disabled_s * 1e9, 1)
    out["forensics_disabled_overhead_pct"] = round(100.0 * disabled_s / step_s, 4)

    # (b) enabled bundle: sentinel check + ring append + hangwatch
    # arm/disarm per step, all live on private instances
    reg_on = obs.Registry(enabled=True)
    rec_on = obs.FlightRecorder(registry=reg_on)
    sent = TrainingSentinels(SentinelConfig(), registry=reg_on, recorder=rec_on)
    hw = obs.HangWatch(registry=reg_on, recorder=rec_on, name="bench-hangwatch")
    try:
        n_iter = 2_000

        def enabled_pass(base: int) -> float:
            t0 = time.perf_counter()
            for i in range(base, base + n_iter):
                tok = hw.arm("train_step", 60.0, step=i)
                rec_on.record("step", step=i, wall_ms=1.0)
                sent.check(i, 0.5)
                hw.disarm(tok)
            return (time.perf_counter() - t0) / n_iter

        # min of 3 passes: scheduler jitter must not manufacture a bar miss
        enabled_s = min(enabled_pass(r * n_iter) for r in range(3))
    finally:
        hw.close()
    out["forensics_enabled_bundle_us"] = round(enabled_s * 1e6, 2)
    out["forensics_enabled_overhead_pct"] = round(100.0 * enabled_s / step_s, 4)
    out["forensics_step_wall_ms"] = round(step_s * 1e3, 3)

    # (c) injected-NaN detection latency at the trainer's sync cadence:
    # NaN enters the batch at inject_step; the halt sentinel may only look
    # every sync_every steps (the loss_sync contract), so detection lands
    # at the next sync point — report both the step gap and the wall gap
    tmp = tempfile.mkdtemp(prefix="dsml_forensics_bench_")
    reg_nan = obs.Registry(enabled=True)
    rec_nan = obs.FlightRecorder(registry=reg_nan, directory=tmp)
    sent = TrainingSentinels(
        SentinelConfig(nonfinite="halt"), registry=reg_nan, recorder=rec_nan,
    )
    sync_every, inject_step = 8, 20
    nan_x = jnp.asarray(np.full_like(x_host, np.nan))
    pp, oo = p0, o0
    trip_step = bundle = None
    t_inject = None
    try:
        for k in range(1, 65):
            if k == inject_step:
                t_inject = time.perf_counter()
            pp, oo, ls = fused_fn(pp, oo, nan_x if k >= inject_step else xb)
            rec_nan.record("step", step=k)
            if k % sync_every == 0:
                try:
                    sent.check(k, float(ls))
                except SentinelTripped as e:
                    trip_step, bundle = k, e.bundle
                    out["forensics_nan_detect_ms"] = round(
                        (time.perf_counter() - t_inject) * 1e3, 3
                    )
                    break
        if trip_step is None:
            out["forensics_nan_error"] = "sentinel never tripped"
        else:
            out["forensics_nan_inject_step"] = inject_step
            out["forensics_nan_trip_step"] = trip_step
            out["forensics_nan_detect_steps"] = trip_step - inject_step
            out["forensics_nan_sync_every"] = sync_every
            if bundle:
                with open(os.path.join(bundle, "MANIFEST.json")) as f:
                    manifest = json.load(f)
                out["forensics_bundle_events"] = manifest["event_count"]
                out["forensics_bundle_files"] = manifest["files"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    out["forensics_note"] = (
        "disabled/enabled rows are the trainer's per-step forensic bundle "
        "cost vs a fused step (<1% bar each); the NaN row injects at step "
        f"{inject_step} and detection is bounded by the sync cadence"
    )
    return out


def bench_chaos() -> dict:
    """Chaos-survival section (docs/ELASTIC.md): the scripted ≥3-kill /
    1-restore schedule plus seeded-random schedules on the virtual-8 mesh
    (subprocess, same pattern as :func:`bench_bucket_sweep`), reporting
    recovery-time p50/p99, the goodput under chaos vs its documented
    floor, lost/redone work, and the bit-identity + zero-token-loss
    verdicts. Virtual-CPU: recovery times are control-plane + re-shard +
    recompile walls, the survival INVARIANTS are platform-independent."""
    code = "import bench; bench._chaos_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=".",
            timeout=max(min(600.0, _budget_left()), 120.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "chaos_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {f"chaos_{k}": v for k, v in res.items()}
        out["chaos_note"] = (
            "virtual-8 CPU mesh: survival invariants (zero lost steps, "
            "bit-identical replay grow-back, zero token loss) are "
            "platform-independent; recovery walls are CPU re-shard + "
            "recompile, not ICI"
        )
        return out
    except Exception as e:  # never fail the bench on the secondary section
        return {"chaos_error": repr(e)[:200]}


def _chaos_main() -> None:
    """Subprocess entry for :func:`bench_chaos`: forces the virtual-8 CPU
    mesh, runs the scripted + seeded schedules, prints one JSON line."""
    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    from dsml_tpu.runtime import chaos

    report = chaos.run_smoke(n_steps=24, seeds=(1, 2, 3), serving=True)
    violations = chaos.verify(report)
    runs = [(k, v) for k, v in report.items()
            if isinstance(v, dict) and "steps_completed" in v]
    out = {
        "recovery_p50_ms": report.get("recovery_p50_ms"),
        "recovery_p99_ms": report.get("recovery_p99_ms"),
        "recovery_samples": report.get("recovery_samples"),
        "runs": len(runs),
        "kills_total": sum(r["kills"] for _, r in runs),
        "bit_identical_runs": sum(1 for _, r in runs if r["bit_identical"]),
        "goodput_min": min(r["goodput"] for _, r in runs),
        "goodput_floor": report["goodput_floor"],
        "redone_steps_total": sum(r["redone_steps"] for _, r in runs),
        "scripted_goodput": report["scripted"]["goodput"],
        "scripted_recoveries": report["scripted"]["n_recoveries"],
        "serving_token_mismatches": report["serving"]["token_mismatches"],
        "serving_scale_events": report["serving"]["scale_events"],
        "violations": violations,
    }
    print(json.dumps(out))


def bench_migration() -> dict:
    """Shard-migration section (docs/ELASTIC.md § Multi-host recovery):
    the two-host (subprocess donor) shrink over P2P streams — shard-motion
    MB/s, recovery p50/p99 split MIGRATION vs CHECKPOINT-FALLBACK, the
    dropped-stream resume and corrupt-chunk CRC verdicts. Virtual-CPU:
    stream walls are loopback gRPC + CRC, the delivery/integrity
    INVARIANTS are platform-independent."""
    code = "import bench; bench._migration_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=".",
            timeout=max(min(600.0, _budget_left()), 120.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "migration_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {f"migration_{k}": v for k, v in res.items()}
        out["migration_note"] = (
            "virtual-8 CPU, subprocess donor over loopback gRPC: MB/s is "
            "stream+CRC wall, not ICI; bit-identity and CRC-abort verdicts "
            "are platform-independent"
        )
        return out
    except Exception as e:  # never fail the bench on the secondary section
        return {"migration_error": repr(e)[:200]}


def _migration_main() -> None:
    """Subprocess entry for :func:`bench_migration`: forces the virtual-8
    CPU mesh, runs the migration smoke with repeated timing pairs, prints
    one JSON line."""
    import numpy as np

    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    from dsml_tpu.runtime import chaos

    report = chaos.run_migration_smoke(reps=3)
    violations = chaos.verify_migration(report)
    clean = report.get("clean", {})
    mig_walls = clean.get("recovery_ms_migration", [])
    fb_walls = clean.get("recovery_ms_fallback", [])
    out = {
        "mb_s": clean.get("mb_s"),
        "migrated_pieces": clean.get("migrated_pieces"),
        "migrated_bytes": clean.get("migrated_bytes"),
        "bit_identical_to_fallback": clean.get("bit_identical_to_fallback"),
        "recovery_migration_p50_ms": (
            round(float(np.percentile(mig_walls, 50)), 3) if mig_walls else None
        ),
        "recovery_migration_p99_ms": (
            round(float(np.percentile(mig_walls, 99)), 3) if mig_walls else None
        ),
        "recovery_fallback_p50_ms": (
            round(float(np.percentile(fb_walls, 50)), 3) if fb_walls else None
        ),
        "recovery_fallback_p99_ms": (
            round(float(np.percentile(fb_walls, 99)), 3) if fb_walls else None
        ),
        "drop_resumed": report.get("drop", {}).get("resumed"),
        "corrupt_integrity_failures": report.get("corrupt", {}).get(
            "integrity_failures"
        ),
        "corrupt_fallback_kind": report.get("corrupt", {}).get("controller_kind"),
        "violations": violations,
    }
    print(json.dumps(out))


def bench_serving_fleet() -> dict:
    """Disaggregated-serving section (docs/SERVING.md): the prefill/decode
    fleet vs N independent monolithic batchers at EQUAL chip count, under
    shared Poisson + bursty arrival schedules — p50/p99 TTFT, per-token
    latency (TPOT + decode inter-emission gap), aggregate tokens/sec, and
    goodput-per-chip from the obs registry. The headline is burst
    ISOLATION: a burst of long prompts inflates the monolithic pool's
    decode p99 (prefill chunks share every decode tick) while the
    disaggregated decode workers' cadence stays flat. Virtual-8 CPU
    subprocess (same pattern as chaos/migration): the latency RATIOS and
    the isolation verdict are the signal, absolute walls are CPU."""
    code = "import bench; bench._serving_fleet_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=".",
            timeout=max(min(600.0, _budget_left()), 120.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "serving_fleet_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {f"serving_fleet_{k}": v for k, v in res.items()}
        out["serving_fleet_note"] = (
            "virtual-8 CPU, single-threaded tick loop: worker dispatches "
            "serialize into one wall clock, which UNDERSTATES isolation — "
            "a real fleet runs workers on their own chips/hosts. Shared "
            "arrival timestamps across variants; equal worker count "
            "(chips) per variant"
        )
        return out
    except Exception as e:  # never fail the bench on the secondary section
        return {"serving_fleet_error": repr(e)[:200]}


def _serving_fleet_main() -> None:
    """Subprocess entry for :func:`bench_serving_fleet`: forces the
    virtual-8 CPU mesh, drives the disaggregated fleet and the monolithic
    pool through IDENTICAL arrival schedules, prints one JSON line.
    ``DSML_SERVING_FLEET_TINY=1`` shrinks the workload for CI smoke."""
    import numpy as np

    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    from dsml_tpu import obs
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.serving import ContinuousBatcher, build_fleet

    tiny = os.environ.get("DSML_SERVING_FLEET_TINY", "").lower() not in (
        "", "0", "false", "off"
    )
    cfg = GPT2Config(vocab_size=256, max_seq=256, n_layer=2, n_head=4,
                     d_model=64, d_ff=128)
    model = GPT2(cfg)
    params = model.init(0)
    obs.enable(forensics=False)
    reg = obs.get_registry()

    # equal chip count: 4 workers per variant — disaggregated splits them
    # 2 prefill + 2 decode, the baseline runs 4 monolithic batchers
    n_prefill, n_decode, chips = 2, 2, 4
    n_slots, chunk = 4, 32
    if tiny:
        n_poisson, rate_hz = 12, 8.0
        n_bg, bg_dt, burst_sizes = 12, 0.05, (5,)
    else:
        n_poisson, rate_hz = 32, 8.0
        n_bg, bg_dt, burst_sizes = 28, 0.05, (7, 7)

    rng = np.random.default_rng(0)

    def prompt(lo, hi):
        return rng.integers(
            0, cfg.vocab_size, (int(rng.integers(lo, hi)),)
        ).astype(np.int32)

    # shared schedules: (arrival_s, prompt, max_new) — FIXED timestamps so
    # every variant faces the identical offered load (the bench_serving
    # lesson: letting each scheduler's tick time reshape arrivals compares
    # mismatched workloads)
    poisson, t = [], 0.0
    for _ in range(n_poisson):
        t += float(rng.exponential(1.0 / rate_hz))
        lo, hi = (8, 25) if rng.random() < 0.7 else (96, 161)
        poisson.append((t, prompt(lo, hi), int(rng.integers(8, 17))))
    # bursty: a steady short-prompt decode stream + bursts of LONG prompts
    # (the head-of-line shape disaggregation exists for)
    bursty = [(0.05 + i * bg_dt, prompt(8, 25), 12) for i in range(n_bg)]
    for j, size in enumerate(burst_sizes):
        bursty += [(0.4 + 0.5 * j, prompt(128, 193), 8) for _ in range(size)]
    bursty.sort(key=lambda a: a[0])

    def tokens_total():
        return sum(r["value"] for r in reg.collect()
                   if r["name"] == "serving_tokens_total")

    class MonoPool:
        """N independent monolithic batchers behind least-loaded dispatch
        — the equal-chip baseline (what PRs 6/7 shipped, horizontally)."""

        def __init__(self, n):
            self.workers = [
                ContinuousBatcher(
                    model, params, n_slots=n_slots,
                    prompt_buckets=(32, 64, 128, 256), prefill_chunk=chunk,
                )
                for _ in range(n)
            ]
            for i, w in enumerate(self.workers):
                w.obs_replica = str(i)
            self.samples, self._out = [], 0

        def submit(self, p, max_new):
            w = min(self.workers,
                    key=lambda b: b.n_queued + b.n_active + b.n_pending)
            w.submit(p, max_new)
            self._out += 1

        def tick(self):
            for w in self.workers:
                if w.n_active or w.n_queued or w.n_pending:
                    w.step()
                    for req in w.collect_requests().values():
                        self._out -= 1
                        ttft = req.first_token_at - req.submitted_at
                        tpot = (
                            (req.finished_at - req.first_token_at)
                            / (len(req.tokens) - 1)
                            if len(req.tokens) > 1 else None
                        )
                        self.samples.append(
                            (ttft, tpot, req.finished_at - req.submitted_at)
                        )

        @property
        def outstanding(self):
            return self._out

        def gaps(self):
            return [g for w in self.workers for g in w._gaps]

        def reset(self):
            self.samples.clear()
            for w in self.workers:
                w.reset_latency_stats()

    class Disagg:
        def __init__(self):
            self.router = build_fleet(
                model, params, n_prefill=n_prefill, n_decode=n_decode,
                prefill_chunk=chunk, n_slots=n_slots,
            )

        def submit(self, p, max_new):
            self.router.submit(p, max_new)

        def tick(self):
            self.router.tick()

        @property
        def outstanding(self):
            return self.router.outstanding

        @property
        def samples(self):
            return self.router.latency_samples

        def gaps(self):
            return self.router.decode_gaps()

        def reset(self):
            self.router.reset_latency_stats()

    def drive(system, schedule):
        """Wall-clock replay of one arrival schedule; returns (wall s,
        tokens emitted per the obs registry)."""
        tok0 = tokens_total()
        t0 = time.monotonic()
        i, n = 0, len(schedule)
        while i < n or system.outstanding:
            now = time.monotonic() - t0
            while i < n and schedule[i][0] <= now:
                system.submit(schedule[i][1], schedule[i][2])
                i += 1
            if i < n and not system.outstanding:
                time.sleep(max(schedule[i][0] - (time.monotonic() - t0), 0.0))
                continue
            system.tick()
        return time.monotonic() - t0, tokens_total() - tok0

    def pct(vals, q):
        return round(float(np.percentile(np.asarray(vals), q)) * 1e3, 2)

    out = {
        "chips": chips, "prefill_workers": n_prefill,
        "decode_workers": n_decode, "mono_workers": chips,
        "slots": n_slots, "chunk": chunk, "tiny": int(tiny),
        "poisson_requests": n_poisson, "bursty_requests": len(bursty),
    }
    systems = {"disagg": Disagg(), "mono": MonoPool(chips)}
    for name, system in systems.items():
        # warm every program the timed runs can hit (multi-chunk prefill,
        # decode, inserts) on THIS instance — its jits are per-closure
        system.submit(prompt(8, 9), 3)
        system.submit(prompt(90, 91), 3)
        while system.outstanding:
            system.tick()
        system.reset()
        for wl, schedule in (("poisson", poisson), ("bursty", bursty)):
            wall, toks = drive(system, schedule)
            samples = list(system.samples)
            ttft = [s[0] for s in samples]
            tpot = [s[1] for s in samples if s[1] is not None]
            gaps = system.gaps()
            row = f"{wl}_{name}"
            out[f"{row}_tokens_per_sec"] = round(toks / wall, 1)
            out[f"{row}_goodput_per_chip"] = round(toks / wall / chips, 2)
            out[f"{row}_ttft_p50_ms"] = pct(ttft, 50)
            out[f"{row}_ttft_p99_ms"] = pct(ttft, 99)
            out[f"{row}_tpot_p50_ms"] = pct(tpot, 50)
            out[f"{row}_tpot_p99_ms"] = pct(tpot, 99)
            out[f"{row}_decode_gap_p50_ms"] = pct(gaps, 50)
            out[f"{row}_decode_gap_p99_ms"] = pct(gaps, 99)
            system.reset()
    out["poisson_throughput_ratio"] = round(
        out["poisson_disagg_tokens_per_sec"]
        / out["poisson_mono_tokens_per_sec"], 3,
    )
    # the headline: decode p99 per-token latency under prompt bursts —
    # monolithic pays prefill chunks inside decode ticks, the fleet doesn't
    out["burst_isolation_speedup"] = round(
        out["bursty_mono_decode_gap_p99_ms"]
        / max(out["bursty_disagg_decode_gap_p99_ms"], 1e-6), 2,
    )
    print(json.dumps(out))


def bench_request_tracing() -> dict:
    """Request-tracing + SLO section (docs/OBSERVABILITY.md § Request
    tracing & SLO budgets): (1) the per-request tracing bill — mint a
    TraceContext + the span/flow/exemplar call sites one request adds —
    measured against a decode tick (< 1% bar, tracing ENABLED; the
    disabled path is the usual one-branch no-op); (2) the PR 10 burst
    schedule driven through an SLO-classed fleet with tracing on,
    reporting per-class burn status and the p99 TAIL-ATTRIBUTION verdict
    (which stage — queue/prefill/handoff/first-decode/decode — dominates
    the tail, with the worst request's trace_id as the exemplar); (3) the
    exemplar/flow-link verdicts: a tail-bucket ``serving_ttft_ms`` sample
    resolves to a real request's trace_id and the request's flow chain is
    fully linked (start → steps → end). Virtual-8 CPU subprocess like
    the serving_fleet section: verdicts and ratios are the signal."""
    code = "import bench; bench._request_tracing_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=".",
            timeout=max(min(600.0, _budget_left()), 120.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "request_tracing_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {f"request_tracing_{k}": v for k, v in res.items()}
        out["request_tracing_note"] = (
            "virtual-8 CPU: per_request_trace_us is the FULL lifetime "
            "tracing bill of one request (mint + spans + flows + SLO "
            "record + exemplar), gated against a serving-representative "
            "decode tick (6-layer d=256 model — far smaller than any "
            "production decode model, so the pct OVERestimates real "
            "deployments'); tail-attribution / exemplar / flow-link "
            "verdicts are platform-independent"
        )
        return out
    except Exception as e:  # never fail the bench on the secondary section
        return {"request_tracing_error": repr(e)[:200]}


def _request_tracing_main() -> None:
    """Subprocess entry for :func:`bench_request_tracing`.
    ``DSML_REQUEST_TRACING_TINY=1`` shrinks the workload for CI smoke."""
    import numpy as np

    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    from dsml_tpu import obs
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.obs import TraceContext, get_tracer
    from dsml_tpu.obs.cluster import snapshot, trace_summary
    from dsml_tpu.serving import SLOClass, build_fleet

    tiny = os.environ.get("DSML_REQUEST_TRACING_TINY", "").lower() not in (
        "", "0", "false", "off"
    )
    cfg = GPT2Config(vocab_size=256, max_seq=256, n_layer=2, n_head=4,
                     d_model=64, d_ff=128)
    model = GPT2(cfg)
    params = model.init(0)
    rng = np.random.default_rng(0)
    n_bg, n_burst = (10, 3) if tiny else (24, 6)

    def prompt(lo, hi):
        return rng.integers(
            0, cfg.vocab_size, (int(rng.integers(lo, hi)),)
        ).astype(np.int32)

    def make_fleet():
        return build_fleet(
            model, params, n_prefill=2, n_decode=2, prefill_chunk=32,
            n_slots=4,
            slo_classes=[
                SLOClass("interactive", tpot_budget_ms=250.0,
                         e2e_budget_ms=10_000.0, objective=0.9),
                SLOClass("batch", priority=1, objective=0.9),
            ],
        )

    # arrival schedule: the PR 10 burst shape — a steady short-prompt
    # decode stream plus one burst of LONG prompts (the head-of-line
    # pattern whose p99 the tail attribution must explain)
    schedule = [(0.02 + i * 0.05, prompt(8, 25), 10, "interactive")
                for i in range(n_bg)]
    schedule += [(0.4, prompt(128, 193), 6, "batch") for _ in range(n_burst)]
    schedule.sort(key=lambda a: a[0])

    def drive(fleet):
        t0 = time.monotonic()
        i, n, ticks = 0, len(schedule), 0
        while i < n or fleet.outstanding:
            now = time.monotonic() - t0
            while i < n and schedule[i][0] <= now:
                fleet.submit(schedule[i][1], schedule[i][2],
                             slo=schedule[i][3])
                i += 1
            if i < n and not fleet.outstanding:
                time.sleep(max(schedule[i][0] - (time.monotonic() - t0), 0.0))
                continue
            fleet.tick()
            ticks += 1
        return time.monotonic() - t0, ticks

    out = {"tiny": int(tiny), "requests": len(schedule)}

    def warm(fleet):
        # warm every jit the schedule can hit (multi-chunk prefill,
        # decode, inserts) on THIS instance — its jits are per-closure
        fleet.submit(prompt(8, 9), 3, slo="interactive")
        fleet.submit(prompt(140, 141), 3, slo="batch")
        while fleet.outstanding:
            fleet.tick()
        fleet.reset_latency_stats()
        # the warm requests flowed through the SLO accounting too; their
        # compile-dominated e2e would own each class's p99 tail (the
        # nearest-rank p99 over ~30 requests IS the single worst sample)
        # and miscount {cls}_requests — same isolation rule as the
        # serving_fleet section's reset_latency_stats
        fleet.slo.reset()
        fleet.reset_request_records()
        return fleet

    # ---- leg 1: tracing-disabled baseline ticks ---------------------------
    wall_off, ticks_off = drive(warm(make_fleet()))
    out["ticks_disabled"] = ticks_off
    out["tick_ms_disabled"] = round(wall_off / ticks_off * 1e3, 4)
    # the denominator the <1% bar references: ONE decode-worker tick with
    # a full batch (pure decode quantum — the steady-state unit of serving
    # work a request's tracing bill rides alongside), obs disabled. The
    # fleet A/B above runs a deliberately MICRO model for schedule speed;
    # this leg uses a serving-representative config (6 layers, d=256 —
    # still far below any production decode model, so the resulting pct
    # is an OVERestimate of real deployments') for the denominator
    from dsml_tpu.serving import ContinuousBatcher

    rep_cfg = GPT2Config(vocab_size=1024, max_seq=256, n_layer=6, n_head=8,
                         d_model=256, d_ff=1024)
    rep_model = GPT2(rep_cfg)
    dw = ContinuousBatcher(rep_model, rep_model.init(0), n_slots=4)
    for _ in range(4):
        dw.submit(prompt(8, 25), 200)
    dw.step()  # admissions + warm decode program
    t0 = time.monotonic()
    n_decode_ticks = 50 if not tiny else 20
    for _ in range(n_decode_ticks):
        dw.step()
    out["decode_tick_ms"] = round(
        (time.monotonic() - t0) / n_decode_ticks * 1e3, 4
    )

    # ---- leg 2: the per-request tracing bill (enabled) --------------------
    obs.enable(forensics=False)
    from dsml_tpu.obs.slo import SLOSpec, SLOTracker

    reg = obs.get_registry()
    tracer = get_tracer()
    hist = reg.histogram("bench_trace_ms", labels=("replica",))
    slo_tracker = SLOTracker([
        SLOSpec("bench", objective=0.9, ttft_budget_ms=100.0,
                tpot_budget_ms=50.0, e2e_budget_ms=1000.0)
    ])
    reps = 2000 if not tiny else 500
    stages = {"queue": 0.01, "prefill": 0.02, "handoff": 0.001,
              "first_decode": 0.01, "decode": 0.05}
    t0 = time.perf_counter()
    for _ in range(reps):
        # everything ONE request ADDS across its lifetime with tracing on:
        # mint + submit span/flow + prefill-chunk span + 3 hop flows +
        # first-token/retire marks + the SLO record + exemplar deltas
        ctx = TraceContext.mint()
        with tracer.request_span("router_submit", ctx, flow="start"):
            pass
        with tracer.request_span("prefill_chunk", ctx, frid=0, start=0):
            pass
        tracer.flow("prefill_handoff", ctx, phase="step")
        tracer.flow("decode_inject", ctx, phase="step")
        tracer.instant("serving_first_token", trace_id=ctx.trace_id)
        tracer.flow("serving_retire", ctx, phase="end")
        slo_tracker.record("bench", ttft_ms=50.0, tpot_ms=20.0,
                           e2e_ms=500.0, trace_id=ctx.trace_id,
                           stages=stages)
        # the TTFT/TPOT observes themselves pre-date this layer; tracing
        # adds only the exemplar attachment — one representative observe
        # stands in (conservatively: the whole call, not just the delta)
        hist.observe(1.0, exemplar=ctx.trace_id, replica="0")
    per_request_us = (time.perf_counter() - t0) / reps * 1e6
    tracer.reset()
    reg.reset()
    out["per_request_trace_us"] = round(per_request_us, 3)
    out["trace_overhead_pct"] = round(
        per_request_us / (out["decode_tick_ms"] * 1e3) * 100.0, 4
    )

    # ---- leg 3: burst schedule with tracing ON, SLO + tail verdicts -------
    fleet = warm(make_fleet())
    wall_on, ticks_on = drive(fleet)
    out["ticks_enabled"] = ticks_on
    out["tick_ms_enabled"] = round(wall_on / ticks_on * 1e3, 4)
    rep = fleet.slo.report()
    tail_ok = 1
    for name, row in rep.items():
        out[f"{name}_requests"] = row["requests"]
        out[f"{name}_goodput_requests"] = row["good_requests"]
        out[f"{name}_burn_status"] = row["status"]
        tail = row.get("tail")
        if tail is None:
            tail_ok = 0
            continue
        out[f"{name}_p99_ms"] = tail["threshold_ms"]
        out[f"{name}_dominant_stage"] = tail["dominant_stage"]
        out[f"{name}_dominant_share"] = tail["dominant_share"]
        out[f"{name}_tail_trace_id"] = tail["worst_trace_id"]
        if not tail.get("worst_trace_id"):
            tail_ok = 0
    out["tail_attribution_ok"] = tail_ok

    # exemplar verdict: a tail-bucket serving_ttft_ms sample must resolve
    # to a trace the router actually retired
    known = {r["trace_id"] for r in fleet.request_records.values()}
    exemplar_ok = 0
    for rec in obs.get_registry().collect():
        if rec["name"] != "serving_ttft_ms":
            continue
        for ex in (rec.get("exemplars") or {}).values():
            if ex.get("trace_id") in known:
                exemplar_ok = 1
    out["ttft_exemplar_ok"] = exemplar_ok

    # flow-link verdict: some retired request's chain is fully linked
    summary = trace_summary(snapshot(role="bench")["trace"])
    linked = sum(
        1 for tid, row in summary.items()
        if tid in known and row["flow"].get("s") and row["flow"].get("f")
        and row["flow"].get("t")
    )
    out["flow_linked_requests"] = linked
    out["flow_links_ok"] = int(linked > 0)
    obs.disable()
    print(json.dumps(out))


def bench_paged_kv() -> dict:
    """Paged int4 KV-cache section (docs/SERVING.md § Paged KV): the paged
    batcher vs the dense-cache batcher at EQUAL HBM budget. Rows:
    analytic bytes accounting (f32 dense rows vs int4 pages with per-row
    scales → the capacity ratio), a measured concurrency leg (the paged
    pool actually holding ≥4× the dense slot count in flight at the dense
    cache's byte budget, greedy tokens BIT-IDENTICAL to the dense batcher
    running the same int4 codec), the PR 10 burst schedule's p99
    decode-gap A/B at equal slot count, and a page-size sweep (the
    docs/TUNING.md defaults' provenance). Virtual-8 CPU subprocess like
    the serving_fleet section: ratios and verdicts are the signal."""
    code = "import bench; bench._paged_kv_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=".",
            timeout=max(min(600.0, _budget_left()), 120.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "paged_kv_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {f"paged_kv_{k}": v for k, v in res.items()}
        out["paged_kv_note"] = (
            "virtual-8 CPU: capacity ratios + bit-identity verdicts are "
            "the signal; absolute walls are CPU (the HBM-bandwidth win of "
            "int4 pages needs real chips). Equal analytic HBM budget per "
            "variant; identical arrival schedules"
        )
        return out
    except Exception as e:  # never fail the bench on the secondary section
        return {"paged_kv_error": repr(e)[:200]}


def _paged_kv_main() -> None:
    """Subprocess entry for :func:`bench_paged_kv`.
    ``DSML_PAGED_KV_TINY=1`` shrinks the workload for CI smoke."""
    import numpy as np

    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.ops.quantization import kv_row_bytes
    from dsml_tpu.serving import ContinuousBatcher

    tiny = os.environ.get("DSML_PAGED_KV_TINY", "").lower() not in (
        "", "0", "false", "off"
    )
    cfg = GPT2Config(vocab_size=256, max_seq=256, n_layer=2, n_head=4,
                     d_model=64, d_ff=128)
    model = GPT2(cfg)
    import dataclasses as _dc

    model_i4 = GPT2(_dc.replace(cfg, kv_quant="int4"))
    params = model.init(0)
    hd = cfg.d_model // cfg.n_head
    chunk = 32
    n_dense_slots = 4
    page_size = 16

    # ---- analytic bytes accounting (exact, not sampled) ----
    def dense_slot_bytes(mode):
        return cfg.n_layer * 2 * cfg.n_head * cfg.max_seq * kv_row_bytes(hd, mode)

    def page_bytes(mode):
        return cfg.n_layer * 2 * cfg.n_head * page_size * kv_row_bytes(hd, mode)

    hbm_budget = n_dense_slots * dense_slot_bytes(None)  # the f32 dense cache
    n_pages_at_budget = hbm_budget // page_bytes("int4")
    out = {
        "dense_slot_bytes_f32": dense_slot_bytes(None),
        "page_bytes_int4": page_bytes("int4"),
        "hbm_budget_bytes": hbm_budget,
        "pages_at_budget": int(n_pages_at_budget),
        "page_size": page_size, "dense_slots": n_dense_slots,
        # worst case: every sequence reserves the full max_seq
        "capacity_ratio_analytic": round(
            (n_pages_at_budget * page_size) / (n_dense_slots * cfg.max_seq), 2
        ),
        "tiny": int(tiny),
    }

    # ---- measured concurrency at equal HBM: the paged pool (sized to the
    # dense budget) holds >= 4x the dense slot count in flight ----
    n_paged_slots = 4 * n_dense_slots
    rng = np.random.default_rng(0)
    n_req = 24 if tiny else 40
    max_new = 12
    prompts = [rng.integers(1, cfg.vocab_size, int(rng.integers(10, 40)))
               .astype(np.int32) for _ in range(n_req)]

    def peak_concurrency(batcher):
        rids = [batcher.submit(p, max_new) for p in prompts]
        peak = 0
        for _ in range(100_000):
            if (not batcher.n_queued and not batcher.n_injected
                    and batcher.n_active == 0 and batcher.n_pending == 0):
                break
            batcher.step()
            peak = max(peak, batcher.n_active)
        return rids, batcher.collect(), peak

    dense_i4 = ContinuousBatcher(model_i4, params, n_slots=n_dense_slots,
                                 prefill_chunk=chunk)
    d_rids, d_toks, d_peak = peak_concurrency(dense_i4)
    paged = ContinuousBatcher(
        model, params, n_slots=n_paged_slots, prefill_chunk=chunk,
        paged_kv="int4", page_size=page_size,
        n_pages=int(n_pages_at_budget),
    )
    p_rids, p_toks, p_peak = peak_concurrency(paged)
    out["dense_peak_concurrent"] = d_peak
    out["paged_peak_concurrent"] = p_peak
    out["measured_concurrency_ratio"] = round(p_peak / max(d_peak, 1), 2)
    out["greedy_bit_identical"] = int(all(
        p_toks[a] == d_toks[b] for a, b in zip(p_rids, d_rids)
    ))

    # ---- PR 10 burst schedule at equal slot count: p99 decode gap A/B
    # (paged gather + int4 codec vs the dense int4 cache) ----
    n_bg, bg_dt = (10, 0.05) if tiny else (24, 0.05)
    burst_sizes = (4,) if tiny else (6, 6)
    bursty = [(0.05 + i * bg_dt,
               rng.integers(1, cfg.vocab_size, int(rng.integers(8, 25)))
               .astype(np.int32), 12) for i in range(n_bg)]
    for j, size in enumerate(burst_sizes):
        bursty += [(0.4 + 0.5 * j,
                    rng.integers(1, cfg.vocab_size, int(rng.integers(128, 193)))
                    .astype(np.int32), 8) for _ in range(size)]
    bursty.sort(key=lambda a: a[0])

    def drive_burst(batcher):
        t0 = time.monotonic()
        i, n = 0, len(bursty)
        while i < n or batcher.n_active or batcher.n_queued or batcher.n_pending:
            now = time.monotonic() - t0
            while i < n and bursty[i][0] <= now:
                batcher.submit(bursty[i][1], bursty[i][2])
                i += 1
            if i < n and not (batcher.n_active or batcher.n_queued
                              or batcher.n_pending):
                time.sleep(max(bursty[i][0] - (time.monotonic() - t0), 0.0))
                continue
            batcher.step()
        batcher.collect()
        return list(batcher._gaps)

    for name, batcher in (
        ("dense", ContinuousBatcher(model_i4, params, n_slots=n_dense_slots,
                                    prefill_chunk=chunk)),
        ("paged", ContinuousBatcher(model, params, n_slots=n_dense_slots,
                                    prefill_chunk=chunk, paged_kv="int4",
                                    page_size=page_size,
                                    n_pages=int(n_pages_at_budget))),
    ):
        # warm the programs off the clock
        batcher.submit(prompts[0], 3)
        batcher.submit(rng.integers(1, cfg.vocab_size, 130).astype(np.int32), 3)
        while batcher.n_active or batcher.n_queued or batcher.n_pending:
            batcher.step()
        batcher.collect()
        batcher.reset_latency_stats()
        gaps = drive_burst(batcher)
        out[f"burst_{name}_gap_p50_ms"] = round(
            float(np.percentile(gaps, 50)) * 1e3, 2)
        out[f"burst_{name}_gap_p99_ms"] = round(
            float(np.percentile(gaps, 99)) * 1e3, 2)
    out["burst_gap_p99_ratio"] = round(
        out["burst_paged_gap_p99_ms"]
        / max(out["burst_dense_gap_p99_ms"], 1e-6), 3)

    # ---- page-size sweep (docs/TUNING.md provenance): decode-tick wall
    # + capacity at the same byte budget per page size ----
    sweep_sizes = (8, 16) if tiny else (8, 16, 32)
    sweep_prompts = prompts[: (8 if tiny else 16)]
    for ps in sweep_sizes:
        npg = int(hbm_budget // (cfg.n_layer * 2 * cfg.n_head * ps
                                 * kv_row_bytes(hd, "int4")))
        b = ContinuousBatcher(model, params, n_slots=n_dense_slots,
                              prefill_chunk=chunk, paged_kv="int4",
                              page_size=ps, n_pages=npg)
        rids = [b.submit(p, max_new) for p in sweep_prompts]
        while b.n_queued or b.n_active or b.n_pending:
            b.step()  # warm + fill
        b.collect()
        walls = []
        rids = [b.submit(p, max_new) for p in sweep_prompts]
        while b.n_queued or b.n_active or b.n_pending:
            t0 = time.monotonic()
            b.step()
            walls.append(time.monotonic() - t0)
        b.collect()
        out[f"sweep_page{ps}_tick_p50_ms"] = round(
            float(np.percentile(walls, 50)) * 1e3, 3)
        out[f"sweep_page{ps}_capacity_tokens"] = npg * ps

    # ---- speculative acceptance: adaptive window on a repetitive
    # workload (acceptance high -> wide windows) vs a random one ----
    rep_prompts = [np.tile(rng.integers(1, 50, 6).astype(np.int32), 4)
                   for _ in range(4)]
    spec = ContinuousBatcher(
        model, params, n_slots=2, prefill_chunk=chunk, speculative_window=6,
        speculative_adaptive=True, paged_kv="int4", page_size=page_size,
        n_pages=int(n_pages_at_budget),
    )
    for p in rep_prompts:
        spec.submit(p, 16)
    spec.run()
    out["spec_accept_rate"] = (
        round(spec.accept_ewma, 3) if spec.accept_ewma is not None else None
    )
    out["spec_windows_used"] = {str(k): v
                                for k, v in sorted(spec.spec_window_used.items())}
    # the speedup diagnostic: verify dispatches per emitted token — plain
    # decode would pay 1.0 (an untrained model's near-repetitive greedy
    # chain keeps acceptance high here; the adaptive NARROWING path is
    # pinned white-box in tests, where acceptance can be forced low)
    toks_emitted = 4 * 16
    out["spec_ticks_per_token"] = round(spec.n_spec_ticks / toks_emitted, 3)
    print(json.dumps(out))


def bench_paged_attention() -> dict:
    """Pallas paged-attention section (docs/SERVING.md § Paged KV,
    PR 14): the gather-free decode kernel vs the XLA ``pool[page_table]``
    gather. Rows: the EXACT analytic per-tick HBM bytes A/B at rising
    live-page fraction (the kernel's bill is live-shaped, the gather's
    table-shaped — ``ops.paged_attention.paged_hbm_bytes``), measured
    decode-tick p50 at the same fractions, a kernel-vs-gather greedy
    bit-identity verdict (the kernel runs interpreted off-TPU), a tp=2
    paged capacity leg (head-sharded pool, tokens identical, ≥4× per-chip
    capacity), and the eviction-preemption pressure verdict (tokens
    identical, zero leaks, completes where reservation would wait).
    Virtual-8 CPU subprocess: the analytic accounting and the verdicts
    are the signal; the HBM-traffic win itself needs real chips."""
    code = "import bench; bench._paged_attention_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=".",
            timeout=max(min(600.0, _budget_left()), 120.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "paged_attention_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {f"paged_attention_{k}": v for k, v in res.items()}
        out["paged_attention_note"] = (
            "virtual-8 CPU: analytic HBM A/B + bit-identity/capacity/"
            "preemption verdicts are the signal; CPU tick walls ride the "
            "XLA gather (the kernel interprets off-TPU) so the live-"
            "fraction traffic win itself needs real chips"
        )
        return out
    except Exception as e:  # never fail the bench on the secondary section
        return {"paged_attention_error": repr(e)[:200]}


def _paged_attention_main() -> None:
    """Subprocess entry for :func:`bench_paged_attention`.
    ``DSML_PAGED_ATTENTION_TINY=1`` shrinks the workload for CI smoke."""
    import numpy as np

    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    import jax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.ops.paged_attention import paged_hbm_bytes
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh
    from dsml_tpu.serving import ContinuousBatcher

    tiny = os.environ.get("DSML_PAGED_ATTENTION_TINY", "").lower() not in (
        "", "0", "false", "off"
    )
    cfg = GPT2Config(vocab_size=256, max_seq=256, n_layer=2, n_head=4,
                     d_model=64, d_ff=128)
    model = GPT2(cfg)
    params = model.init(0)
    hd = cfg.d_model // cfg.n_head
    page_size = 16
    n_pt = cfg.max_seq // page_size
    chunk = 32
    n_slots = 4
    out = {"tiny": int(tiny), "page_size": page_size, "n_slots": n_slots}

    # ---- analytic per-tick HBM bytes, one layer, xla gather vs pallas
    # kernel, at rising live-page fraction (exact program-structure
    # counts — ops.paged_attention.paged_hbm_bytes) ----
    total_pages = n_slots * n_pt
    for frac in (25, 50, 100):
        live = max(total_pages * frac // 100, 1)
        for impl in ("xla", "pallas"):
            out[f"hbm_{impl}_bytes_live{frac}"] = paged_hbm_bytes(
                n_slots=n_slots, n_pt=n_pt, page_size=page_size,
                n_kv_head=cfg.n_head, head_dim=hd, mode="int4",
                live_pages=live, impl=impl,
            )
    # the kernel's bill is LIVE-shaped: exact linearity in live pages;
    # the gather's is TABLE-shaped: flat. Both checked right here so a
    # codegen drift can't ship a stale table
    p25, p50, p100 = (out[f"hbm_pallas_bytes_live{f}"] for f in (25, 50, 100))
    x25, x100 = out["hbm_xla_bytes_live25"], out["hbm_xla_bytes_live100"]
    # live steps are +25% and +50% of the table: exact linearity means the
    # second increment is exactly twice the first
    out["hbm_pallas_live_shaped_ok"] = int(
        p100 - p50 == 2 * (p50 - p25) > 0 and p100 < x100
    )
    out["hbm_xla_table_shaped_ok"] = int(x25 == x100)
    out["hbm_reduction_at_live25"] = round(x25 / p25, 1)

    # ---- measured decode-tick p50 at rising live-page fraction (CPU
    # runs the gather; its wall should be ~flat vs live fraction — the
    # table-shaped cost the kernel exists to remove on chips) ----
    rng = np.random.default_rng(0)
    max_new = 8
    for frac in (25, 100) if tiny else (25, 50, 100):
        depth = max(int(cfg.max_seq * frac / 100) - max_new - 1, 8)
        b = ContinuousBatcher(model, params, n_slots=n_slots,
                              prefill_chunk=chunk, paged_kv="int4",
                              page_size=page_size, n_pages=total_pages + 1)
        prompts = [rng.integers(1, cfg.vocab_size, depth).astype(np.int32)
                   for _ in range(n_slots)]
        for p in prompts:
            b.submit(p, max_new)
        while b.n_pending or b.n_queued:  # admit everyone (compile off-clock)
            b.step()
        walls = []
        while b.n_active:
            t0 = time.monotonic()
            b.step()
            walls.append(time.monotonic() - t0)
        b.collect()
        out[f"tick_p50_ms_live{frac}"] = round(
            float(np.percentile(walls, 50)) * 1e3, 3)

    # ---- kernel parity: greedy tokens bit-identical pallas vs xla
    # (interpreted kernel off-TPU — slow, so a small drain) ----
    par_prompts = [rng.integers(1, cfg.vocab_size,
                                int(rng.integers(8, 40))).astype(np.int32)
                   for _ in range(2 if tiny else 4)]

    def drain(impl, **kw):
        os.environ["DSML_PAGED_ATTN"] = impl
        try:
            b = ContinuousBatcher(model, params, n_slots=2,
                                  prefill_chunk=chunk, paged_kv="int4",
                                  page_size=page_size, n_pages=40, **kw)
            rids = [b.submit(p, 4) for p in par_prompts]
            got = b.run()
            return [got[r] for r in rids]
        finally:
            os.environ.pop("DSML_PAGED_ATTN", None)

    out["pallas_parity_ok"] = int(drain("xla") == drain("pallas"))

    # ---- tp=2 paged capacity leg: the pool's head axis shards over tp,
    # tokens identical to single-device paged, and the ≥4× capacity
    # ratio holds PER CHIP (each chip carries 1/tp of every page) ----
    from dsml_tpu.ops.quantization import kv_row_bytes

    tp_prompts = [rng.integers(1, cfg.vocab_size,
                               int(rng.integers(8, 40))).astype(np.int32)
                  for _ in range(3)]

    def drain_tp(mesh=None):
        b = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=chunk,
                              paged_kv="int4", page_size=page_size,
                              n_pages=40, mesh=mesh)
        rids = [b.submit(p, 5) for p in tp_prompts]
        got = b.run()
        return [got[r] for r in rids]

    mesh = build_mesh(MeshSpec(tp=2), jax.devices()[:2])
    out["tp2_tokens_identical_ok"] = int(drain_tp() == drain_tp(mesh))
    per_chip_slot = cfg.n_layer * 2 * (cfg.n_head // 2) * cfg.max_seq \
        * kv_row_bytes(hd, None)
    per_chip_page = cfg.n_layer * 2 * (cfg.n_head // 2) * page_size \
        * kv_row_bytes(hd, "int4")
    budget = n_slots * per_chip_slot
    out["tp2_capacity_ratio"] = round(
        (budget // per_chip_page) * page_size / (n_slots * cfg.max_seq), 2)

    # ---- eviction preemption under pressure: a pool ~1/4 the worst case
    # still drains with tokens identical to the uncontended run, zero
    # leaks — and records the throughput next to the reservation tier's
    # (same small pool: reservation WAITS where preemption overlaps) ----
    pr_prompts = [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
                  for l in (17, 9, 13, 21)]
    pr_budgets = [14, 14, 12, 12]
    # chunk 16: the admission grid hugs the prompt, so the decode budget
    # has to GROW pages mid-flight — that growth is what the 5-page pool
    # starves into evictions
    big = ContinuousBatcher(model, params, n_slots=4, prefill_chunk=16,
                            paged_kv="int4", page_size=page_size, n_pages=60)
    ref_rids = [big.submit(p, n) for p, n in zip(pr_prompts, pr_budgets)]
    ref_got = big.run()
    want = [ref_got[r] for r in ref_rids]

    def pressured(preemption):
        b = ContinuousBatcher(model, params, n_slots=4, prefill_chunk=16,
                              paged_kv="int4", page_size=page_size,
                              n_pages=5, preemption=preemption)
        rids = [b.submit(p, n) for p, n in zip(pr_prompts, pr_budgets)]
        t0 = time.monotonic()
        got = b.run()
        wall = time.monotonic() - t0
        toks = sum(len(got[r]) for r in rids)
        return [got[r] for r in rids], toks / max(wall, 1e-9), b

    res_toks, res_tput, _ = pressured(False)
    pre_toks, pre_tput, bp = pressured(True)
    out["preempt_tokens_identical_ok"] = int(pre_toks == want == res_toks)
    out["preempt_eviction_events"] = bp.n_preemptions
    out["preempt_no_leak_ok"] = int(bp.free_pages == bp.n_pages - 1)
    out["preempt_tokens_per_sec"] = round(pre_tput, 1)
    out["reserve_tokens_per_sec"] = round(res_tput, 1)
    print(json.dumps(out))


def bench_kernel_fusion() -> dict:
    """Deep-fusion section (docs/TUNING.md § Kernel fusion, PR 16): the
    three env-gated fusions A/B'd against their parity oracles. Rows:
    decode-tick p50 with the double-buffered paged kernel vs the
    single-buffer kernel at rising live-page fraction, per-hop ring
    walls fused (sendahead) vs unfused plus the analytic MXU-idle
    fraction the fusion exists to close, the weight-byte compression
    rows (>=3.9x int8 / >=7.8x int4 at d=768 — the acceptance floors),
    and bit-identity verdicts for all three fusions. Virtual-8 CPU
    subprocess: both paged kernels run INTERPRETED off-TPU and the
    in-ring hop lowers to the same ppermute schedule, so every
    DMA-overlap row carries an explicit provenance label
    ("interpret"/"analytic") — the overlap win itself needs real chips
    (the ROADMAP evidence sweep's kernel_fusion leg)."""
    code = "import bench; bench._kernel_fusion_main()"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=".",
            timeout=max(min(600.0, _budget_left()), 120.0),
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            return {
                "kernel_fusion_error": (
                    f"rc={proc.returncode}; stderr tail: {proc.stderr[-300:]}"
                )
            }
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {f"kernel_fusion_{k}": v for k, v in res.items()}
        out["kernel_fusion_note"] = (
            "virtual-8 CPU: bit-identity verdicts, compression floors and "
            "analytic idle accounting are the signal; interpret-mode tick "
            "walls execute DMAs synchronously, so the pipelined-vs-single "
            "and fused-vs-unfused wall deltas only mean anything on chips"
        )
        return out
    except Exception as e:  # never fail the bench on the secondary section
        return {"kernel_fusion_error": repr(e)[:200]}


def _kernel_fusion_main() -> None:
    """Subprocess entry for :func:`bench_kernel_fusion`.
    ``DSML_KERNEL_FUSION_TINY=1`` shrinks the workload for CI smoke."""
    import numpy as np

    from dsml_tpu.utils.platform import configure_platform

    configure_platform("cpu", 8)
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.ops.attention import attention
    from dsml_tpu.ops.paged_attention import paged_vmem_bytes
    from dsml_tpu.ops.quantization import quantize_weight_blocks
    from dsml_tpu.ops.ring_attention import (
        causal_keep_fraction, ring_attention, ring_kv_wire_bytes,
    )
    from dsml_tpu.serving import ContinuousBatcher

    tiny = os.environ.get("DSML_KERNEL_FUSION_TINY", "").lower() not in (
        "", "0", "false", "off"
    )
    out: dict = {"tiny": int(tiny)}

    # ---- (1) paged double buffering: decode-tick p50 pipelined vs
    # single-buffer at rising live fraction. Off-TPU both kernels
    # INTERPRET (DMAs synchronous): provenance below says so ----
    cfg = GPT2Config(vocab_size=256, max_seq=128, n_layer=1, n_head=4,
                     d_model=64, d_ff=128)
    model = GPT2(cfg)
    params = model.init(0)
    page_size = 16
    n_slots = 2
    rng = np.random.default_rng(0)
    out["page_size"] = page_size
    out["n_slots"] = n_slots
    out["dma_overlap_provenance"] = "interpret"
    max_new = 4
    fracs = (25,) if tiny else (25, 100)
    for frac in fracs:
        depth = max(int(cfg.max_seq * frac / 100) - max_new - 1, 8)
        prompts = [rng.integers(1, cfg.vocab_size, depth).astype(np.int32)
                   for _ in range(n_slots)]
        for pipe, tag in (("0", "single"), ("1", "pipelined")):
            os.environ["DSML_PAGED_ATTN"] = "pallas"
            os.environ["DSML_PAGED_ATTN_PIPELINE"] = pipe
            try:
                b = ContinuousBatcher(
                    model, params, n_slots=n_slots, prefill_chunk=32,
                    paged_kv="int4", page_size=page_size,
                    n_pages=n_slots * cfg.max_seq // page_size + 1)
                for p in prompts:
                    b.submit(p, max_new)
                while b.n_pending or b.n_queued:  # compile off-clock
                    b.step()
                walls = []
                while b.n_active:
                    t0 = time.monotonic()
                    b.step()
                    walls.append(time.monotonic() - t0)
                b.collect()
            finally:
                os.environ.pop("DSML_PAGED_ATTN", None)
                os.environ.pop("DSML_PAGED_ATTN_PIPELINE", None)
            out[f"tick_p50_ms_live{frac}_{tag}"] = round(
                float(np.percentile(walls, 50)) * 1e3, 3)
    # the analytic overlap claim the interpreter can't show: the slot
    # ring keeps the NEXT page's DMA in flight during this page's math,
    # at a VMEM working set the budget guard sizes (the "_bytes" rows
    # are structure, never perf-gated)
    hd = cfg.d_model // cfg.n_head
    out["paged_vmem_pipelined_bytes"] = paged_vmem_bytes(
        page_size, hd, "int4", pipeline=True)
    out["paged_vmem_single_bytes"] = paged_vmem_bytes(
        page_size, hd, "int4", pipeline=False)

    # ---- (2) in-ring fused KV hop: per-hop wall fused (sendahead) vs
    # unfused on the virtual cp=4 mesh + the analytic MXU-idle fraction
    # the fusion closes on chips. CPU lowers both schedules to the same
    # ppermute program, hence the analytic label ----
    cp, s, h, hdr = 4, (128 if tiny else 256), 2, 16
    mesh = Mesh(np.asarray(jax.devices()[:cp]).reshape(cp), ("cp",))
    spec = P(None, None, "cp", None)
    qkv = [jnp.asarray(rng.standard_normal((1, h, s, hdr)), jnp.float32)
           for _ in range(3)]

    def ring_fn(fused):
        return jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "cp", True, fused=fused),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False))

    hops = cp - 1
    ring_rows = {}
    for fused, tag in ((None, "unfused"), ("sendahead", "fused")):
        fn = ring_fn(fused)
        ring_rows[tag] = np.asarray(fn(*qkv))  # compile + parity capture
        reps = 3 if tiny else 5
        t0 = time.monotonic()
        for _ in range(reps):
            jax.block_until_ready(fn(*qkv))
        wall = (time.monotonic() - t0) / reps
        # per hop, both directions together (the bidirectional ring runs
        # 2 streams of cp-1 hops concurrently)
        out[f"ring_hop_ms_{tag}"] = round(wall / hops * 1e3, 3)
    out["ring_fused_bit_identical_ok"] = int(
        np.array_equal(ring_rows["fused"], ring_rows["unfused"]))
    out["ring_hop_provenance"] = "analytic"
    # analytic MXU-idle fraction per hop on chips: the exposed hop is the
    # KV shard's wire time; fused, it hides behind the hop's flash math —
    # report the exposed fraction the unfused schedule leaves idle
    # assuming compute-bound hops (v4 ICI ~50 GB/s/link, MXU at the flash
    # kernel's measured ~40% MFU — the labels matter, not the constants)
    wire = ring_kv_wire_bytes(s // cp, cp, h, hdr) / hops  # bytes per hop
    flops_hop = 4 * 1 * h * (s // cp) * s * hdr * causal_keep_fraction(cp)
    ici_s = wire / 50e9
    mxu_s = flops_hop / (275e12 * 0.4)
    out["ring_mxu_idle_frac_unfused_analytic"] = round(
        ici_s / (ici_s + mxu_s), 4)
    out["ring_mxu_idle_frac_fused_analytic"] = 0.0

    # ---- (3) dequant-fused weights: compression rows at real dims
    # (d=768 — the acceptance floors) + kernel-vs-oracle parity ----
    from dsml_tpu.ops.quantization import (
        dequantize_weight_blocks, quantized_matmul,
    )

    w = jnp.asarray(rng.standard_normal((768, 768)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((8, 768)), jnp.float32)
    parity = True
    for scheme in ("int8", "int4"):
        qwt = quantize_weight_blocks(w, scheme)
        out[f"weight_compression_{scheme}"] = round(
            qwt.dense_bytes / qwt.hbm_bytes, 2)
        got = np.asarray(quantized_matmul(x, qwt))
        ref = np.asarray(x @ dequantize_weight_blocks(qwt))
        err = float(np.max(np.abs(got - ref)) /
                    max(float(np.max(np.abs(ref))), 1e-9))
        parity = parity and err < 1e-5
    out["weight_fused_parity_ok"] = int(parity)
    out["weight_quant_provenance"] = "interpret"
    print(json.dumps(out))


def bench_cluster() -> dict:
    """Cluster-observability section (``docs/OBSERVABILITY.md`` § Cluster):

    (a) DISABLED overhead guard: the per-step instrumentation the
        aggregation plane rides on (a span + a metric write against a
        disabled registry — scraping is pull-driven and costs the stepping
        process NOTHING per step beyond these call sites), vs a fused
        step: ``cluster_disabled_overhead_pct`` must stay < 1%;
    (b) live-scrape overhead: the same steps while an aggregator hammers
        the process's ``/cluster.json`` endpoint from a background thread
        (far above any sane scrape cadence) — the endpoint serializes on
        its own daemon thread, so the step path should barely notice;
    (c) plane micro-costs: merge wall for a 3-process × many-series fleet,
        one scrape round-trip (HTTP, with the clock handshake), stitch
        wall + event count;
    (d) the regress gate self-check: ``obs.regress`` against the
        ``BENCH_r*.json`` history in the working directory (rc 2 when
        there is none), and the calibrated collective profile written for
        the cost-model planner is summarized here.
    """
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dsml_tpu import obs
    from dsml_tpu.obs import cluster as obs_cluster
    from dsml_tpu.obs import regress as obs_regress
    from dsml_tpu.obs.spans import SpanTracer

    out: dict = {}
    rng = np.random.default_rng(0)
    d, batch = 256, 64
    params = {
        f"p{i}": jnp.asarray(rng.standard_normal((d, d)), jnp.float32)
        for i in range(4)
    }
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    xb = jnp.asarray(rng.standard_normal((batch, d)).astype(np.float32))

    def loss_fn(p, x):
        h = x
        for i in range(4):
            h = jnp.tanh(h @ p[f"p{i}"])
        return jnp.mean(h * h)

    def fused(p, o, x):
        loss, g = jax.value_and_grad(loss_fn)(p, x)
        up, o = optimizer.update(g, o, p)
        return optax.apply_updates(p, up), o, loss

    fused_fn = jax.jit(fused)
    p0, o0, loss = fused_fn(params, opt_state, xb)
    float(loss)

    def step_wall(k: int = 40) -> float:
        pp, oo = p0, o0
        t0 = time.perf_counter()
        for _ in range(k):
            pp, oo, ls = fused_fn(pp, oo, xb)
        float(ls)
        return (time.perf_counter() - t0) / k

    step_s = min(step_wall() for _ in range(3))
    out["cluster_step_wall_ms"] = round(step_s * 1e3, 3)

    # (a) disabled: the per-step span + metric write the plane aggregates,
    # against a DISABLED private registry — one branch each
    reg_off = obs.Registry(enabled=False)
    trc_off = SpanTracer(registry=reg_off)
    ctr_off = reg_off.counter("cluster_bench_steps_total")
    n_iter = 100_000
    t0 = time.perf_counter()
    for i in range(n_iter):
        with trc_off.span("step"):
            ctr_off.inc()
    disabled_s = (time.perf_counter() - t0) / n_iter
    out["cluster_disabled_instrument_ns"] = round(disabled_s * 1e9, 1)
    out["cluster_disabled_overhead_pct"] = round(100.0 * disabled_s / step_s, 4)

    # (b) live scrape hammering from a background thread while stepping
    reg_on = obs.Registry(enabled=True)
    trc_on = SpanTracer(registry=reg_on)
    for i in range(64):
        reg_on.histogram("warm_ms", labels=("k",)).observe(float(i), k=i % 8)
    srv = obs.start_metrics_server(registry=reg_on, role="bench",
                                   tracer=trc_on)
    stop = threading.Event()
    scrapes = [0]

    def hammer():
        import urllib.request

        while not stop.is_set():
            with urllib.request.urlopen(
                f"{srv.address}/cluster.json", timeout=5.0
            ) as resp:
                resp.read()
            scrapes[0] += 1

    thread = threading.Thread(target=hammer, daemon=True)
    thread.start()
    try:
        def step_wall_scraped(k: int = 40) -> float:
            pp, oo = p0, o0
            t0 = time.perf_counter()
            for _ in range(k):
                with trc_on.span("step"):
                    pp, oo, ls = fused_fn(pp, oo, xb)
            float(ls)
            return (time.perf_counter() - t0) / k

        scraped_s = min(step_wall_scraped() for _ in range(3))
    finally:
        stop.set()
        thread.join(timeout=5.0)
    out["cluster_scrape_hammer_count"] = scrapes[0]
    out["cluster_scraped_step_wall_ms"] = round(scraped_s * 1e3, 3)
    out["cluster_scrape_overhead_pct"] = round(
        max(100.0 * (scraped_s - step_s) / step_s, 0.0), 2
    )

    # (c) merge / scrape / stitch micro-costs on a synthetic 3-process fleet
    def synth_snap(pid: int) -> dict:
        reg = obs.Registry(enabled=True)
        trc = SpanTracer(registry=reg)
        for i in range(64):
            reg.counter("c_total", labels=("k",)).inc(1.0, k=i % 16)
            reg.histogram("h_ms", labels=("k",)).observe(float(i), k=i % 16)
            with trc.span(f"phase{i % 4}"):
                pass
        snap = obs_cluster.snapshot(role="bench", registry=reg, tracer=trc)
        snap["pid"] = pid  # fake distinct processes
        return snap

    snaps = [synth_snap(100 + i) for i in range(3)]
    out["cluster_merge_ms"] = round(_p50_wall(
        lambda: obs_cluster.merge_snapshots(snaps).collect(), reps=9
    ) * 1e3, 3)
    # scrape timing into a THROWAWAY aggregator per rep — accumulating the
    # timing reps would make the stitch row measure 9 duplicate snapshots
    # of this process instead of the documented 3-process fleet
    out["cluster_scrape_roundtrip_ms"] = round(_p50_wall(
        lambda: obs_cluster.ClusterAggregator().scrape(srv.address), reps=9
    ) * 1e3, 3)
    srv.stop()
    agg = obs_cluster.ClusterAggregator()
    for s in snaps:
        agg.add(s)
    t0 = time.perf_counter()
    stitched = agg.stitched_trace()
    out["cluster_stitch_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
    out["cluster_stitch_events"] = len(stitched["traceEvents"])

    # (d) the regress gate over the working directory's history (self-check:
    # the newest record vs the full history must be clean) + the calibrated
    # collective profile for the cost-model planner
    profile_path = os.path.join(".", "collective_profile.json")
    rc = obs_regress.main([
        "--history", "BENCH_r*.json", "--profile", profile_path,
    ])
    out["cluster_regress_selfcheck_rc"] = rc
    try:
        with open(profile_path) as f:
            prof = json.load(f)
        out["cluster_profile_constants"] = len(prof.get("constants", {}))
        for k, v in prof.get("derived", {}).items():
            out[f"cluster_profile_{k}"] = round(v, 4)
    except OSError:
        out["cluster_profile_error"] = "profile not written"
    out["cluster_note"] = (
        "disabled row = the per-step span+metric call sites the pull-driven "
        "aggregation rides on (scrapes cost the step path nothing); scrape "
        "row hammers /cluster.json far above any sane cadence; regress rc=0 "
        "means the working directory's BENCH history gates itself clean"
    )
    return out


def _section_gpt2_small() -> dict:
    res = _gpt2_train_throughput(batch=8, seq=1024, xent_chunk=0)
    return {f"gpt2_{k}": v for k, v in res.items()}


def _section_gpt2_large() -> dict:
    """Scale row: GPT-2-large (774M) trains on ONE chip — params + Adam
    moments + grads land ~11 GB in the 16 GB HBM with no remat, and MFU
    climbs past medium's (the vocab/small-matmul tail keeps shrinking).
    The heaviest compile in the bench — runs late and budget-gated."""
    big = _gpt2_train_throughput(batch=4, seq=1024, xent_chunk=8192, k_extra=2,
                                 reps=5, preset="large")
    return {
        "gpt2_large_tokens_per_sec": big["tokens_per_sec"],
        "gpt2_large_mfu": big["mfu"],
        "gpt2_large_step_ms": big["step_ms"],
        "gpt2_large_params": big["params"],
        "gpt2_large_batch": big["batch"],
        "gpt2_large_compile_s": big["compile_s"],
    }


def _section_gpt2_xl() -> dict:
    """Extreme-scale row: GPT-2-XL (1.56B) trains on ONE 16 GB chip —
    bf16 params (3.1 GB) + grads + ADAFACTOR's factored optimizer state
    (AdamW's two f32 moment trees alone would be 12.5 GB) + remat'd
    activations. Analytic MFU does NOT count the remat recompute, so the
    hardware is busier than the number suggests."""
    xl = _gpt2_train_throughput(batch=1, seq=1024, xent_chunk=8192, k_extra=2,
                                reps=5, preset="xl", optimizer="adafactor",
                                remat=True)
    return {
        "gpt2_xl_tokens_per_sec": xl["tokens_per_sec"],
        "gpt2_xl_mfu": xl["mfu"],
        "gpt2_xl_mfu_hw": xl["mfu_hw"],
        "gpt2_xl_step_ms": xl["step_ms"],
        "gpt2_xl_params": xl["params"],
        "gpt2_xl_optimizer": "adafactor",
        "gpt2_xl_remat": True,
        "gpt2_xl_compile_s": xl["compile_s"],
        "gpt2_xl_note": (
            "1.5B on one 16 GB chip: adafactor factored state + remat; "
            "analytic MFU excludes remat recompute, mfu_hw counts it "
            "(what the MXU actually executed)"
        ),
    }


def _section_gpt2_seq32k() -> dict:
    """Maximum-length stretch row: 32,768 tokens in ONE sequence on one
    chip. SELECTIVE remat first (remat='mlp': attention activations kept —
    re-running the O(s²·d) flash forward is what made whole-block remat
    expensive at this length — only the cheap FFN recomputes); falls back
    to whole-block remat if the kept activations don't fit HBM. 16k fits
    without any remat, see gpt2_seq16k."""
    mlp_error = None
    try:
        long = _gpt2_train_throughput(batch=1, seq=32768, xent_chunk=4096,
                                      k_extra=2, reps=4, remat="mlp")
        mode = "mlp"
    except Exception as e:
        # fall back ONLY on the memory-exhaustion shape — any other error
        # must surface, not silently double the heaviest
        # single-chip compile
        memory_shaped = any(s in str(e) for s in
                            ("RESOURCE_EXHAUSTED", "Out of memory", "OOM",
                             "Allocation", "exceeds the memory"))
        if not memory_shaped:
            raise
        mlp_error = repr(e)[:200]
        long = _gpt2_train_throughput(batch=1, seq=32768, xent_chunk=4096,
                                      k_extra=2, reps=4, remat=True)
        mode = True
    out32 = {
        "gpt2_seq32k_tokens_per_sec": long["tokens_per_sec"],
        "gpt2_seq32k_mfu": long["mfu"],
        "gpt2_seq32k_mfu_hw": long["mfu_hw"],
        "gpt2_seq32k_step_ms": long["step_ms"],
        "gpt2_seq32k_remat": mode,
        "gpt2_seq32k_compile_s": long["compile_s"],
        "gpt2_seq32k_note": (
            "32k context, single chip; remat='mlp' = selective (FFN-only "
            "recompute, attention activations kept); analytic MFU excludes "
            "the recompute, mfu_hw counts it"
        ),
    }
    if mlp_error is not None:
        out32["gpt2_seq32k_mlp_remat_oom"] = mlp_error
    return out32


def _section_llama1b() -> dict:
    """Second-family scale row: TinyLlama-1.1B (22x2048, GQA 32q/4kv,
    SwiGLU, untied head) trains on ONE chip with AdamW — the parallel
    stack and bench methodology are model-generic, and the analytic FLOP
    count below is Llama's own (GQA-shrunk kv projections, 3-matmul
    SwiGLU, untied unembedding)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dsml_tpu.models.llama import Llama, LlamaConfig

    batch, seq, k_extra, reps = 2, 2048, 2, 5
    cfg = dataclasses.replace(
        LlamaConfig.tinyllama_1b(), dtype="bfloat16", max_seq=seq, xent_chunk=8192
    )
    model = Llama(cfg)
    dev = jax.devices()[0]
    params = jax.device_put(model.init(0), dev)
    n_params = model.n_params(params)
    optimizer = optax.adamw(3e-4, weight_decay=0.01)
    opt_state = jax.device_put(optimizer.init(params), dev)
    rng = np.random.default_rng(0)
    x = jax.device_put(
        jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32), dev
    )
    y = jnp.roll(x, -1, axis=1)

    step_s, timing_mode, compile_s, loss = _timed_train_steps(
        model, optimizer, params, opt_state, x, y, k_extra, reps
    )

    T = batch * seq
    # Llama's own analytic count (GQA-shrunk kv, 3-matmul SwiGLU, untied
    # unembedding) via the shared estimator in models/common
    from dsml_tpu.models.common import transformer_train_flops

    achieved = transformer_train_flops(cfg, T, seq, gated_mlp=True) / step_s
    peak = _peak_flops(dev)
    return {
        "llama1b_tokens_per_sec": round(T / step_s, 1),
        "llama1b_mfu": round(achieved / peak, 4),
        "llama1b_step_ms": round(step_s * 1e3, 2),
        "llama1b_params": n_params,
        "llama1b_batch": batch,
        "llama1b_seq": seq,
        "llama1b_compile_s": round(compile_s, 1),
        "llama1b_timing_mode": timing_mode,
        "llama1b_final_loss": round(loss, 3),
        "llama1b_model": "TinyLlama-1.1B L22 d2048 GQA32q/4kv bf16 adamw",
    }


def _section_gpt2_seq16k() -> dict:
    """Long-context stretch row: 16k tokens in ONE sequence on one chip,
    no remat (flash + chunked-vocab CE keep activations inside HBM) —
    double the seq8k row's length; the auto 1024x1024 flash blocks apply."""
    long = _gpt2_train_throughput(batch=1, seq=16384, xent_chunk=4096, k_extra=2, reps=5)
    return {
        "gpt2_seq16k_tokens_per_sec": long["tokens_per_sec"],
        "gpt2_seq16k_mfu": long["mfu"],
        "gpt2_seq16k_step_ms": long["step_ms"],
        "gpt2_seq16k_compile_s": long["compile_s"],
    }


def _section_gpt2_seq8k() -> dict:
    long = _gpt2_train_throughput(batch=1, seq=8192, xent_chunk=8192, k_extra=3, reps=6)
    return {
        "gpt2_seq8k_tokens_per_sec": long["tokens_per_sec"],
        "gpt2_seq8k_mfu": long["mfu"],
        "gpt2_seq8k_step_ms": long["step_ms"],
        "gpt2_seq8k_compile_s": long["compile_s"],
    }


def _section_gpt2_medium() -> dict:
    med = _gpt2_train_throughput(batch=4, seq=1024, xent_chunk=0, k_extra=3, reps=6,
                                 preset="medium")
    return {
        "gpt2_medium_tokens_per_sec": med["tokens_per_sec"],
        "gpt2_medium_mfu": med["mfu"],
        "gpt2_medium_step_ms": med["step_ms"],
        "gpt2_medium_params": med["params"],
    }


# Independently runnable bench sections (``python bench.py --section NAME``):
# each runs ONE measurement in this process, on whatever ``jax.devices()``
# gives, and prints its rows as JSON.
_SECTIONS = {
    "gpt2": _section_gpt2_small,
    "gpt2_seq8k": _section_gpt2_seq8k,
    "gpt2_seq16k": _section_gpt2_seq16k,
    "gpt2_seq32k": _section_gpt2_seq32k,
    "gpt2_large": _section_gpt2_large,
    "gpt2_xl": _section_gpt2_xl,
    "llama1b": _section_llama1b,
    "gpt2_decode": bench_gpt2_decode,
    "gpt2_medium": _section_gpt2_medium,
    "mnist": bench_mnist,
    "allreduce": bench_ring_allreduce,
    "realtext": bench_gpt2_realtext,
    "serving": bench_serving,
    "bucket_sweep": bench_bucket_sweep,  # virtual-8 sweep; no TPU rows
    "quant_sweep": bench_quant_sweep,  # virtual-8 quantized-collective grid
    #                                    + q8+EF parity verdicts; no TPU rows
    "checkpoint": bench_checkpoint,
    "obs": bench_obs,
    "forensics": bench_forensics,
    "chaos": bench_chaos,  # virtual-8 kill/restore schedules; no TPU rows
    "serving_fleet": bench_serving_fleet,  # disaggregated prefill/decode
    "request_tracing": bench_request_tracing,  # per-request tracing bill +
    #                                            SLO burn/tail-attribution
    #                                            verdicts; virtual-8
    "paged_kv": bench_paged_kv,  # paged int4 KV cache vs dense at equal HBM
    #                                        A/B vs monolithic; virtual-8
    "paged_attention": bench_paged_attention,  # Pallas paged kernel vs XLA
    #                     gather: analytic live-vs-table HBM A/B, parity +
    #                     tp=2 capacity + eviction verdicts; virtual-8
    "kernel_fusion": bench_kernel_fusion,  # deep-fusion A/B: pipelined
    #                     paged DMA, in-ring fused KV hop, dequant-fused
    #                     matmuls — bit-identity + compression floors +
    #                     analytic idle accounting; virtual-8
    "cluster": bench_cluster,  # aggregation-plane overhead + regress gate
    "migration": bench_migration,  # P2P shard-motion MB/s + recovery split
    "long_context": bench_long_context,  # cp=8 ring-attention ladder to 128k
    #                                      + exact KV wire bytes + headroom
    #                                      + parity verdicts; virtual-8
    "memory": bench_memory,  # memory-ledger reconciliation + analytic-vs-
    #                          measured rung cross-check + OOM bundle +
    #                          fleet merge + <1% disabled bar; virtual-8
}


def _device_labels() -> dict:
    import jax

    dev = jax.devices()[0]
    return {
        "device": str(dev),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }


def run_section(name: str) -> int:
    if name not in _SECTIONS:
        print(f"unknown section {name!r}; choose from {sorted(_SECTIONS)}",
              file=sys.stderr)
        return 4
    from dsml_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    labels = _device_labels()
    rows = _SECTIONS[name]()
    print(json.dumps({"section": name, **labels, "rows": rows}))
    return 0


# main()'s sections in run order: (errors/skip key, callable, seconds the
# budget gate wants left). The flagship runs ungated. allreduce comes before
# the serving rows: it is the SECOND BASELINE metric and the beyond-reference
# rows must not budget-starve it.
_MAIN_SECTIONS = (
    ("mnist", bench_mnist, 150),
    ("gpt2_realtext", bench_gpt2_realtext, 240),
    ("allreduce", bench_ring_allreduce, 90),
    ("serving", bench_serving, 240),
    ("llama1b", _section_llama1b, 420),
    ("allreduce_virtual8", bench_ring_virtual8, 120),
    ("checkpoint", bench_checkpoint, 120),
    ("obs", bench_obs, 120),
    ("forensics", bench_forensics, 90),
    ("bucket_sweep", bench_bucket_sweep, 240),
    ("quant_sweep", bench_quant_sweep, 300),
    ("serving_fleet", bench_serving_fleet, 300),
    ("paged_kv", bench_paged_kv, 300),
    ("request_tracing", bench_request_tracing, 200),
    ("memory", bench_memory, 150),
)


def main() -> int:
    """Run every section in this one process on whatever ``jax.devices()``
    gives; print the one JSON line; return nonzero if a section raised."""
    import traceback

    from dsml_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    extras: dict = _device_labels()
    errors: dict = {}

    def run(key, fn):
        try:
            extras.update(fn())
        except Exception as e:  # record, keep the other sections' rows, exit 1
            traceback.print_exc()
            errors[key] = repr(e)[:300]

    run("gpt2", bench_gpt2)
    for key, fn, need_s in _MAIN_SECTIONS:
        if not _skip_for_budget(extras, key, need_s):
            run(key, fn)
    _assemble_and_print(extras, errors)
    return 1 if errors else 0


def _assemble_and_print(extras: dict, errors: dict) -> None:
    if errors:
        extras["errors"] = errors

    # provenance labels: what ran on what data
    extras["data_provenance"] = {
        "gpt2": "synthetic random tokens — throughput/MFU measurement only, no quality claim",
        "gpt2_realtext": extras.get(
            "gpt2_realtext_provenance", "row did not run (see errors/skips)"
        ),
        "mnist": (
            "t10k split 8k train / 2k test + shift augmentation (the 60k "
            "train-images blob is stripped from the reference mirror); "
            "reference protocol is 60k/10k, so accuracies are not "
            "apples-to-apples"
        ),
        "cifar10_resnet_example": "synthetic data by default (examples/train_cifar_resnet.py)",
        "serving": (
            extras.get("serving_model", "row did not run (see errors/skips)")
            + "; synthetic prompts, streaming-arrival mix"
        ),
        "allreduce_real_chip": f"{extras['platform']} device, 1 MB payload",
        "allreduce_virtual8": "8-device virtual CPU mesh — harness proof, not ICI",
        "bucket_sweep": (
            "8-device virtual CPU mesh — relative bucket-size signal for "
            "the DSML_BUCKET_MB default, not ICI"
        ),
        "quant_sweep": (
            "8-device virtual CPU mesh — _ms cells relative signal only; "
            "wire_reduction rows analytic byte counts; parity rows measured "
            "loss trajectories vs the fp32 ring"
        ),
    }

    if "gpt2_tokens_per_sec" in extras:
        achieved = extras["gpt2_achieved_tflops"] * 1e12
        headline = {
            "metric": "gpt2_tokens_per_sec_per_chip",
            "value": extras["gpt2_tokens_per_sec"],
            "unit": "tokens/s/chip",
            # achieved training FLOP/s vs the reference's achieved FLOP/s
            # (its only published throughput number; definition in extras)
            "vs_baseline": round(achieved / REFERENCE_FLOPS_PER_SEC, 1),
        }
        extras["vs_baseline_definition"] = (
            "achieved training FLOP/s ÷ reference's achieved FLOP/s "
            "(6 × 101,770 params × 1,250 MNIST samples/s on its laptop CPU)"
        )
    else:  # the flagship raised (see extras["errors"]): no headline value
        headline = {
            "metric": "gpt2_tokens_per_sec_per_chip",
            "value": None,
            "unit": "tokens/s/chip",
            "vs_baseline": None,
        }

    headline["extras"] = extras
    print(json.dumps(headline))
    sys.stdout.flush()


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--section":
        sys.exit(run_section(sys.argv[2]))
    sys.exit(main())
