"""Shared model utilities: initializers, classification losses, the
FSDP spec transform every family's ``param_specs`` routes through, and
weight-only int8 quantization for the serving path."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "he_init", "softmax_xent", "count_correct", "with_fsdp", "fsdp_spec_fn",
    "quantize_weights_int8", "quantize_weights_blocked", "maybe_dequant",
    "qmatmul", "transformer_train_flops", "mlp_train_flops",
]


def transformer_train_flops(cfg, n_tokens: int, seq: int,
                            gated_mlp: bool = False) -> int:
    """Analytic matmul FLOPs for ONE training step over ``n_tokens`` tokens
    at sequence length ``seq`` — the PaLM-appendix accounting (fwd matmuls
    + causal attention term; bwd = 2×fwd; remat recompute NOT counted).
    This is the FLOP numerator behind every MFU ``obs.step_stats``
    reports, kept here so model families cannot drift apart in their
    accounting (``benchmarks/tests/test_flops.py`` holds the benchmark's
    ``flops.py::train_flops`` equal to it).

    ``cfg`` needs ``n_layer / n_head / d_model / d_ff / vocab_size``;
    GQA shrinks the k/v projections via ``n_kv_head`` when present.
    ``gated_mlp=True`` counts the 3-matmul SwiGLU form (Llama), else the
    2-matmul in/out form (GPT-2)."""
    T = int(n_tokens)
    d, ff, L, V = cfg.d_model, cfg.d_ff, cfg.n_layer, cfg.vocab_size
    kv_frac = getattr(cfg, "n_kv_head", cfg.n_head) / cfg.n_head
    mlp_mats = 3 if gated_mlp else 2
    fwd = L * (
        2 * T * d * d                       # q projection
        + int(2 * 2 * T * d * d * kv_frac)  # k and v projections (GQA-shrunk)
        + 2 * T * d * d                     # attention output projection
        + 2 * 2 * T * seq * d // 2          # q·kᵀ and p·v, causal halves the area
        + mlp_mats * 2 * T * d * ff         # MLP matmuls
    ) + 2 * T * d * V                       # unembedding
    return 3 * fwd


def head_dim(cfg) -> int:
    """A head's width: the config's own ``head_dim`` where it names one, else
    ``d_model // n_head``."""
    return getattr(cfg, "head_dim", 0) or cfg.d_model // cfg.n_head


def mlp_train_flops(n_params: int, n_samples: int) -> int:
    """The dense-MLP rule the reference baseline is scored by: 6 FLOPs per
    parameter per sample (fwd 2 + bwd 4)."""
    return 6 * int(n_params) * int(n_samples)

# transformer-block matmul weights both families contract on AXIS 0 —
# the per-output-channel absmax scale is therefore max|w| over axis 0
# (GPT-2: fused wqkv [d, 3, d] keeps a scale per (qkv-slot, channel))
_WQ_KEYS = frozenset({
    "wqkv", "wo", "wq", "wk", "wv",           # attention projections
    "w_in", "w_out", "w_gate", "w_up", "w_down",  # dense MLP
})


def quantize_weights_int8(params: dict) -> dict:
    """Weight-only int8 (w8a16) for SERVING: every transformer-block
    attention/MLP matmul weight becomes ``{"qw": int8, "qs": f32 scale}``
    with per-output-channel absmax scales; embeddings, the unembedding,
    norms, biases, and MoE experts stay full precision (MoE contracts on
    a middle axis and the gate is routing-sensitive — out of scope).

    Decode is weight-HBM-bandwidth-bound, so halving weight bytes vs bf16
    (4x vs f32) raises decode tokens/s; the int8→float convert + scale
    feed the dot operand, which XLA fuses into the matmul read — no
    dequantized weight copy is ever materialized in HBM. Quantized params
    serve the single-device decode surfaces (``generate``, the continuous
    batcher, speculative decode); the TP/shard_map paths expect plain
    leaves matching ``param_specs`` and are not supported."""

    def quant_layer(layer: dict) -> dict:
        out = {}
        for group, leaves in layer.items():
            if group in ("attn", "mlp") and isinstance(leaves, dict):
                out[group] = {
                    k: _quant_leaf(v) if k in _WQ_KEYS else v
                    for k, v in leaves.items()
                }
            else:
                out[group] = leaves
        return out

    def _quant_leaf(w: jax.Array) -> dict:
        a = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0, keepdims=True)
        qs = jnp.where(a > 0, a / 127.0, 1.0)
        qw = jnp.round(w.astype(jnp.float32) / qs).astype(jnp.int8)
        return {"qw": qw, "qs": qs.astype(jnp.float32)}

    return {
        k: ([quant_layer(l) for l in v] if k == "layers" else v)
        for k, v in params.items()
    }


def quantize_weights_blocked(params: dict, scheme: str = "int8",
                             block: int | None = None) -> dict:
    """Serving weight quantization for the DEQUANT-FUSED kernel path: the
    same leaf selection as :func:`quantize_weights_int8`, but each matmul
    weight becomes an ``ops.quantization.QuantizedWeight`` — nibble-packed
    int4 or int8 codes with one f32 scale per (k-block, output channel) —
    consumed by :func:`qmatmul`, which runs the Pallas dequant-fused
    matmul (``quantized_matmul``) instead of letting XLA expand the
    weight. HBM holds the weights at ~4× (int8) / ~8× (int4) under f32
    and the full-width form only ever exists one VMEM tile at a time.
    Same scope limits: single-device serving surfaces only (TP shard_map
    paths expect plain leaves matching ``param_specs``)."""
    from dsml_tpu.ops.quantization import quantize_weight_blocks

    def quant_layer(layer: dict) -> dict:
        out = {}
        for group, leaves in layer.items():
            if group in ("attn", "mlp") and isinstance(leaves, dict):
                out[group] = {
                    k: (quantize_weight_blocks(v, scheme, block)
                        if k in _WQ_KEYS else v)
                    for k, v in leaves.items()
                }
            else:
                out[group] = leaves
        return out

    return {
        k: ([quant_layer(l) for l in v] if k == "layers" else v)
        for k, v in params.items()
    }


def maybe_dequant(w, dtype=None):
    """Matmul-site hook for weight-only int8: plain arrays pass through;
    ``{"qw", "qs"}`` leaves dequantize into the requested dtype (default
    f32) right at the dot operand, where XLA fuses the convert+scale into
    the read instead of materializing a full-width copy."""
    if isinstance(w, dict) and "qw" in w:
        dt = dtype or jnp.float32
        return w["qw"].astype(dt) * w["qs"].astype(dt)
    return w


def qmatmul(x, w, dtype=None):
    """THE matmul-site dispatcher for every weight codec the serving path
    carries: plain arrays and per-channel ``{"qw","qs"}`` dicts keep their
    exact pre-existing lowering (``@`` / einsum on ``maybe_dequant`` — the
    w8a16 fast path), while block-quantized ``QuantizedWeight`` leaves
    route to the Pallas dequant-fused matmul, contracting ``x``'s last
    axis against the weight's first and restoring the weight's trailing
    axes (GPT-2's fused ``wqkv [d, 3, d]`` comes back ``[..., 3, d]``, so
    the einsum call site needs no special casing)."""
    from dsml_tpu.ops.quantization import QuantizedWeight, quantized_matmul

    if isinstance(w, QuantizedWeight):
        lead = x.shape[:-1]
        out = quantized_matmul(x.reshape(-1, x.shape[-1]), w)
        return out.reshape(*lead, *w.shape[1:]).astype(dtype or x.dtype)
    w = maybe_dequant(w, dtype)
    if w.ndim == 3:
        # the fused-QKV form: [b, s, d] · [d, slots, d] — kept as the
        # einsum the site always compiled to
        return jnp.einsum("bsd,dke->bske", x, w)
    return x @ w


def with_fsdp(spec, shape: tuple, fsdp: int, axis: str = "fsdp"):
    """Add ``axis`` to ``spec`` on the first UNSHARDED dim of ``shape`` that
    divides by ``fsdp`` (the ZeRO-3 rule ``parallel.fsdp.fsdp_shardings``
    applies to NamedShardings, here at the PartitionSpec level so it composes
    with TP/PP inside one spec). Leaves with no divisible free dim stay as
    given (replicated over fsdp) — small norms/biases, where sharding buys
    nothing. ``shape`` is the GLOBAL (unstacked) leaf shape; callers state it
    analytically next to the spec, and the placement itself verifies it:
    ``device_put``/``shard_map`` reject indivisible dims, so a drifted shape
    can't silently mis-shard."""
    from jax.sharding import PartitionSpec as P

    if fsdp <= 1:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (p, n) in enumerate(zip(parts, shape)):
        if p is None and n % fsdp == 0 and n >= fsdp:
            parts[i] = axis
            return P(*parts)
    return spec


def fsdp_spec_fn(fsdp: int, axis: str = "fsdp"):
    """``F(spec, *shape)`` adapter over :func:`with_fsdp` — the one-liner
    every ``param_specs`` implementation binds, kept here so the call shape
    can't drift between model families."""
    return lambda spec, *shape: with_fsdp(spec, shape, fsdp, axis)


def he_init(rng: np.random.Generator, *shape: int, fan_in: int) -> jax.Array:
    """He-normal initialization (scale sqrt(2/fan_in)), float32."""
    return jnp.asarray(rng.standard_normal(shape) * np.sqrt(2.0 / fan_in), jnp.float32)


def softmax_xent(logits: jax.Array, y: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy over integer labels."""
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def count_correct(logits: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.sum(jnp.argmax(logits, axis=-1) == y)
