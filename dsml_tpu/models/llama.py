"""Llama-family decoder — the second transformer family on the same mesh
program infrastructure.

The reference never got past an MLP (SURVEY.md §2.3); GPT-2 realizes its
literature roadmap, and this module demonstrates the framework claim that
matters beyond any one model: the parallelism stack (Megatron TP psums,
ring/Ulysses/2D/flash sequence parallelism, GPipe/interleaved/1F1B
pipelines, FSDP, elastic reconfigure) is MODEL-GENERIC. Llama subclasses
:class:`~dsml_tpu.models.gpt2.GPT2` and overrides only the architecture:

- **RMSNorm** instead of LayerNorm (no mean-centering, no bias).
- **RoPE** rotary position embeddings applied to q/k inside attention — no
  learned position table; under sequence parallelism each sp/cp rank rotates
  by its GLOBAL positions (rank · s_local offset), so ring/Ulysses attention
  — including the context-parallel flash ring, ``attn_impl="ring2"``
  (``ops.ring_attention``; parity pinned in tests/test_ring_attention.py) —
  stays exact.
- **SwiGLU** MLP: ``silu(x·w_gate) ⊙ (x·w_up) · w_down`` — gate/up
  column-sharded, down row-sharded (same Megatron psum points as GPT-2).
- **GQA** (grouped-query attention): ``n_kv_head ≤ n_head`` K/V heads,
  repeated to query heads for the shared attention impls; the KV cache holds
  only the kv heads (the GQA serving memory win). TP requires
  ``n_kv_head % tp == 0``.
- **Untied unembedding** (``lm_head``), vocab-sharded like ``wte``.

Everything else — ``loss_spmd`` (vocab-sharded CE / chunked xent), pipeline
integration (``pp_interleave`` included), remat modes (incl. ``"int8"``
compressed), ``generate``/``generate_spmd`` serving, 1F1B — is inherited
unchanged: the subclass overrides the layer math, the mesh machinery never
notices.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dsml_tpu.models.common import head_dim, maybe_dequant, qmatmul
from dsml_tpu.models.gpt2 import GPT2
from dsml_tpu.ops.attention import _NEG_INF

__all__ = ["LlamaConfig", "Llama"]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq: int = 2048
    n_layer: int = 22
    n_head: int = 32
    n_kv_head: int = 4  # GQA: kv heads grouped under query heads
    d_model: int = 2048
    d_ff: int = 5632  # SwiGLU hidden width
    head_dim: int = 0  # a head's width where it is not d_model // n_head (0): q is then n_head·head_dim wide
    dtype: str = "float32"
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    # Mixtral-style expert parallelism: >0 replaces the SwiGLU MLP with the
    # inherited capacity-bounded top-k expert layer (token payloads ride
    # all_to_all over tp — models/gpt2.py::_moe_block; expert MLPs use that
    # layer's GELU form, the routing/dispatch machinery being the point)
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    remat: bool | str = False
    xent_chunk: int = 8192  # the blocked head's vocabulary threshold, 0 = dense (GPT2Config.xent_chunk)
    pp_interleave: int = 1
    # int8 KV cache with per-position scales (see GPT2Config.kv_quant) —
    # stacks with the GQA cache's kv-heads-only memory win
    kv_quant: bool | str = False  # False | True/"int8" | "int4"

    @staticmethod
    def tinyllama_1b() -> "LlamaConfig":
        """TinyLlama-1.1B shape (22×2048, GQA 32q/4kv)."""
        return LlamaConfig()

    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig(
            n_layer=32, n_head=32, n_kv_head=32, d_model=4096, d_ff=11008, max_seq=4096
        )

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, n_layer=32, n_head=32, n_kv_head=8, d_model=4096,
            d_ff=14336, max_seq=8192, rope_theta=500000.0,
        )

    @classmethod
    def by_name(cls, name: str, **tiny_kwargs) -> "LlamaConfig":
        presets = {
            "tiny": cls.tiny,
            "tinyllama_1b": cls.tinyllama_1b,
            "llama2_7b": cls.llama2_7b,
            "llama3_8b": cls.llama3_8b,
            "mixtral_8x7b": cls.mixtral_8x7b,
        }
        if name not in presets:
            raise ValueError(f"unknown Llama preset {name!r}; choose from {sorted(presets)}")
        return presets[name](**tiny_kwargs) if name == "tiny" else presets[name]()

    @staticmethod
    def tiny(vocab_size: int = 512, n_experts: int = 0) -> "LlamaConfig":
        """Test-sized config exercising GQA (8q/2kv), RoPE, SwiGLU."""
        return LlamaConfig(
            vocab_size=vocab_size, max_seq=128, n_layer=2, n_head=8, n_kv_head=2,
            d_model=64, d_ff=128, n_experts=n_experts,
        )

    @staticmethod
    def mixtral_8x7b() -> "LlamaConfig":
        """Mixtral-8x7B shape: Llama-2-7B trunk, 8 experts, top-2 routing."""
        return LlamaConfig(
            n_layer=32, n_head=32, n_kv_head=8, d_model=4096, d_ff=14336,
            max_seq=4096, n_experts=8, expert_top_k=2,
        )


@jax.named_scope("normalize")
def _rms_norm(x, scale, eps=1e-5):
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * scale


def _rope(x: jax.Array, positions: jax.Array, theta: float, head_axis: int = 1) -> jax.Array:
    """Rotary embedding, rotate-half convention. ``x`` [b, h, s, hd] (or
    [b, s, h, hd] with ``head_axis=2``: the projections' own layout),
    ``positions`` [s] GLOBAL token positions (int32) shared across the
    batch, or [b, s] per-row positions (continuous-batching decode, where
    every slot sits at its own depth)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) * 2.0 / hd)  # [half]
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [(b,) s, half]
    if angles.ndim == 2:  # shared positions → broadcast over the batch too
        angles = angles[None]
    cos = jnp.expand_dims(jnp.cos(angles), head_axis)  # one angle for every head
    sin = jnp.expand_dims(jnp.sin(angles), head_axis)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class Llama(GPT2):
    """Llama on the GPT-2 mesh scaffolding (see module docstring)."""

    def __init__(self, config: LlamaConfig | None = None):
        self.config = config or LlamaConfig.tinyllama_1b()
        self._kv_mode()  # a bad kv_quant string fails at construction

    # ---- params ---------------------------------------------------------------

    def init(self, seed: int = 0) -> dict:
        cfg = self.config
        rng = np.random.default_rng(seed)
        dt = jnp.dtype(cfg.dtype)
        q_d, kv_d = cfg.n_head * head_dim(cfg), cfg.n_kv_head * head_dim(cfg)

        def normal(*shape, std=0.02):
            return jnp.asarray(rng.standard_normal(shape) * std, dt)

        res_std = 0.02 / math.sqrt(2 * cfg.n_layer)
        params = {
            "wte": normal(cfg.vocab_size, cfg.d_model),
            "lm_head": normal(cfg.vocab_size, cfg.d_model),
            "rms_f": {"scale": jnp.ones(cfg.d_model, dt)},
            "layers": [
                {
                    "rms_1": {"scale": jnp.ones(cfg.d_model, dt)},
                    "rms_2": {"scale": jnp.ones(cfg.d_model, dt)},
                    "attn": {
                        "wq": normal(cfg.d_model, q_d),
                        "wk": normal(cfg.d_model, kv_d),
                        "wv": normal(cfg.d_model, kv_d),
                        "wo": normal(q_d, cfg.d_model, std=res_std),
                    },
                    **(
                        {"moe": self._moe_param_init(normal, res_std)}
                        if cfg.n_experts
                        else {
                            "mlp": {
                                "w_gate": normal(cfg.d_model, cfg.d_ff),
                                "w_up": normal(cfg.d_model, cfg.d_ff),
                                "w_down": normal(cfg.d_ff, cfg.d_model, std=res_std),
                            }
                        }
                    ),
                }
                for _ in range(cfg.n_layer)
            ],
        }
        return params

    def param_specs(self, pp: bool = False, fsdp: int = 1) -> dict:
        """Megatron sharding: q/k/v/gate/up column-parallel (head split for
        q/k/v), wo/w_down row-parallel, vocab matrices vocab-sharded; with
        ``fsdp > 1`` each leaf is additionally ZeRO-sharded on its first
        free divisible dim (``models.common.with_fsdp``)."""
        from jax.sharding import PartitionSpec as P

        from dsml_tpu.models.common import fsdp_spec_fn

        cfg = self.config
        d, ff, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
        q_d, kv_d = cfg.n_head * head_dim(cfg), cfg.n_kv_head * head_dim(cfg)
        F = fsdp_spec_fn(fsdp)
        layer_spec = {
            "rms_1": {"scale": F(P(), d)},
            "rms_2": {"scale": F(P(), d)},
            "attn": {
                "wq": F(P(None, "tp"), d, q_d),
                "wk": F(P(None, "tp"), d, kv_d),
                "wv": F(P(None, "tp"), d, kv_d),
                "wo": F(P("tp", None), q_d, d),
            },
        }
        if cfg.n_experts:
            layer_spec["moe"] = self._moe_specs(fsdp)
        else:
            layer_spec["mlp"] = {
                "w_gate": F(P(None, "tp"), d, ff),
                "w_up": F(P(None, "tp"), d, ff),
                "w_down": F(P("tp", None), ff, d),
            }
        if pp:
            from dsml_tpu.parallel.pp import pipeline_specs

            layers = pipeline_specs(layer_spec, "pp")
        else:
            layers = [layer_spec for _ in range(cfg.n_layer)]
        return {
            "wte": F(P("tp", None), V, d),
            "lm_head": F(P("tp", None), V, d),
            "rms_f": {"scale": F(P(), d)},
            "layers": layers,
        }

    # ---- architecture hooks ---------------------------------------------------

    def _final_norm(self, params, h):
        return _rms_norm(h, params["rms_f"]["scale"], self.config.rms_eps)

    def _unembed_matrix(self, params):
        return params["lm_head"]

    def _block_closure(self, tp_axis, sp_axis, attn_impl):
        cfg = self.config
        tp_size = lax.axis_size(tp_axis) if tp_axis else 1
        if cfg.n_head % tp_size or cfg.n_kv_head % tp_size:
            raise ValueError(
                f"n_head={cfg.n_head}/n_kv_head={cfg.n_kv_head} not divisible by tp={tp_size}"
            )
        return super()._block_closure(tp_axis, sp_axis, attn_impl)

    @jax.named_scope("embed")
    def _embed_spmd(self, params, tokens, tp_axis=None, sp_axis=None, seq_offset=None):
        """Token embedding only — positions enter through RoPE, not a table."""
        if tp_axis:
            vocab_shard = params["wte"].shape[0]
            tp_rank = lax.axis_index(tp_axis)
            local_ids = tokens - tp_rank * vocab_shard
            in_shard = (local_ids >= 0) & (local_ids < vocab_shard)
            safe_ids = jnp.clip(local_ids, 0, vocab_shard - 1)
            return lax.psum(params["wte"][safe_ids] * in_shard[..., None], tp_axis)
        return params["wte"][tokens]

    @jax.named_scope("rope")
    def _rotate(self, t, positions, head_axis=1):
        """Positions enter here, on q and k (a family without rotary
        overrides with the identity)."""
        return _rope(t, positions, self.config.rope_theta, head_axis)

    def _qkv_gqa(self, layer, x, n_head_local, n_kv_local, positions, head_axis=1):
        """Separate q/k/v projections, head split, RoPE on q/k. Returns
        ``(q, k_kv, v_kv, k_attn, v_attn)``: the kv-head forms (what the
        serving cache stores) and the query-head-repeated forms (what the
        shared MHA attention impls consume) — ONE copy of the GQA math for
        both the training and serving paths. Head-major ``[b, h, s, hd]``,
        or with ``head_axis=2`` the projections' own ``[b, s, h, hd]`` (a
        reshape, no copy: what the packed flash entry reads)."""
        hd = head_dim(self.config)

        def heads(t, n):
            b, s, _ = t.shape
            t = t.reshape(b, s, n, hd)
            return t.transpose(0, 2, 1, 3) if head_axis == 1 else t

        q = heads(qmatmul(x, layer["attn"]["wq"], x.dtype), n_head_local)
        k = heads(qmatmul(x, layer["attn"]["wk"], x.dtype), n_kv_local)
        v = heads(qmatmul(x, layer["attn"]["wv"], x.dtype), n_kv_local)
        q, k = self._rotate(q, positions, head_axis), self._rotate(k, positions, head_axis)
        repeat = n_head_local // n_kv_local
        with jax.named_scope("kv_repeat"):
            ka = jnp.repeat(k, repeat, axis=head_axis) if repeat > 1 else k
            va = jnp.repeat(v, repeat, axis=head_axis) if repeat > 1 else v
        return q, k, v, ka, va

    def _block(self, layer, h, n_head_local, tp_axis, sp_axis, attn_impl):
        with jax.named_scope("attn"):
            h = h + self._attn_block(layer, h, n_head_local, tp_axis, sp_axis, attn_impl)
        return self._ffn(layer, h, tp_axis)

    def _attn_block(self, layer, h, n_head_local, tp_axis, sp_axis, attn_impl):
        cfg = self.config
        n_kv_local = n_head_local * cfg.n_kv_head // cfg.n_head
        s_local = h.shape[1]
        # global positions: this sp rank's sequence shard starts at rank·s_local
        offset = lax.axis_index(sp_axis) * s_local if sp_axis else 0
        positions = offset + jnp.arange(s_local, dtype=jnp.int32)

        x = _rms_norm(h, layer["rms_1"]["scale"], cfg.rms_eps)
        if self._flash_packs(sp_axis, attn_impl, n_head_local):
            from dsml_tpu.ops.flash import flash_attention_packed

            q, _, _, ka, va = self._qkv_gqa(layer, x, n_head_local, n_kv_local, positions, head_axis=2)
            out, _ = flash_attention_packed([t.reshape(*t.shape[:2], -1) for t in (q, ka, va)], q.shape[-1])
        else:
            q, _, _, ka, va = self._qkv_gqa(layer, x, n_head_local, n_kv_local, positions)
            out = self._merge_heads(self._route_attention(q, ka, va, sp_axis, attn_impl))
        out = qmatmul(out, layer["attn"]["wo"], out.dtype)
        if tp_axis:
            out = lax.psum(out, tp_axis)
        return out

    def _mlp_block(self, mlp, x, tp_axis):
        mid = jax.nn.silu(qmatmul(x, mlp["w_gate"], x.dtype)) * qmatmul(x, mlp["w_up"], x.dtype)  # [b, s, ff/tp]
        out = qmatmul(mid, mlp["w_down"], x.dtype)
        if tp_axis:
            out = lax.psum(out, tp_axis)  # Megatron psum #2
        return out

    def _ffn(self, layer, h, tp_axis=None):
        # Mixtral-style MoE: the inherited capacity-bounded top-k expert
        # layer — token payloads ride all_to_all over tp (real EP)
        sub, key = ((self._moe_block, "moe") if self.config.n_experts
                    else (self._mlp_block, "mlp"))

        def ffn(sub_p, scale, hh):
            with jax.named_scope("mlp"):
                return sub(sub_p, _rms_norm(hh, scale, self.config.rms_eps), tp_axis)

        if self.config.remat == "mlp":
            # selective remat, same contract as GPT2._block: attention
            # activations stay saved, only the FFN recomputes in backward
            ffn = jax.checkpoint(ffn)
        return h + ffn(layer[key], layer["rms_2"]["scale"], h)

    def _hidden_spmd(
        self, params, tokens, tp_axis=None, sp_axis=None, attn_impl="ring",
        seq_offset=None, pp_axis=None, n_micro=1,
    ):
        if seq_offset is not None:
            # GPT-2 realizes seq_offset through its wpe table; Llama positions
            # enter via RoPE inside _block, which derives them from the sp
            # rank — an externally supplied offset would be silently ignored
            raise ValueError(
                "Llama forward does not take seq_offset (RoPE positions derive "
                "from the sp shard); use prefill/decode_step for offset decoding"
            )
        return super()._hidden_spmd(
            params, tokens, tp_axis, sp_axis, attn_impl, None, pp_axis, n_micro
        )

    # ---- serving hooks (KV cache holds kv heads only — the GQA memory win) ----
    # prefill/decode_step themselves are inherited: the base loops call these.

    def init_cache(self, batch: int, tp_size: int = 1) -> list:
        cfg = self.config
        if cfg.n_kv_head % tp_size:
            raise ValueError(f"n_kv_head={cfg.n_kv_head} not divisible by tp={tp_size}")
        return [
            self._cache_entry(batch, cfg.n_kv_head // tp_size)
            for _ in range(cfg.n_layer)
        ]

    def _norm1(self, layer, h):
        return _rms_norm(h, layer["rms_1"]["scale"], self.config.rms_eps)

    def _attn_out_bias(self, layer):
        return 0.0

    def _serving_qkv(self, layer, x, positions, tp_size):
        """Thin wrapper over :meth:`_qkv_gqa` (one copy of the GQA math):
        cache forms keep the kv heads, attention forms repeat them."""
        cfg = self.config
        return self._qkv_gqa(
            layer, x, cfg.n_head // tp_size, cfg.n_kv_head // tp_size, positions
        )

    def _decode_attention(self, q, ck, cv, valid, k_s=None, v_s=None):
        """Grouped-query attention against the kv-head cache — query heads
        grouped over their kv head, no materialized repeat; scores
        accumulate f32 via preferred_element_type (no full-cache upcast
        copies on the decode hot path). ``valid`` is [S] (shared depth) or
        [b, S] (per-slot depth, continuous batching); ``k_s``/``v_s``
        [b, kv, S, 1] are the int8 cache's per-position scales, folded in
        after each dot so the dequantize never materializes a full-width
        cache copy (see ``GPT2._cache_attn_inputs``)."""
        b, hq, s, hd = q.shape
        repeat = hq // ck.shape[1]
        qg = q.reshape(b, hq // repeat, repeat, s, hd)
        if k_s is not None:
            # quantized branch upcasts BOTH q·k operands to f32, matching
            # GPT2._decode_attention exactly — the two families' kv_quant
            # feature must apply identical precision (int8 magnitudes are
            # exact in bf16, but the q operand's rounding would differ)
            qg = qg.astype(jnp.float32)
            ck = ck.astype(jnp.float32)
        scores = jnp.einsum(
            "bgrqd,bgkd->bgrqk", qg, ck,
            preferred_element_type=jnp.float32,
        ) * (hd ** -0.5)
        if k_s is not None:
            # [b, kv, S, 1] → [b, kv, 1, 1, S]: per-key-position scale
            scores = scores * jnp.swapaxes(k_s, -1, -2)[:, :, None]
        if valid.ndim == 1:  # [S] shared depth
            vmask = valid[None, None, None, None, :]
        elif valid.ndim == 2:  # [b, S] per-slot depth
            vmask = valid[:, None, None, None, :]
        else:  # [b, q, S] multi-query (chunked prefill)
            vmask = valid[:, None, None, :, :]
        scores = jnp.where(vmask, scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        if v_s is not None:
            probs = probs * jnp.swapaxes(v_s, -1, -2)[:, :, None]
            cv = cv.astype(jnp.float32)
        else:
            probs = probs.astype(cv.dtype)
        # bf16 inputs feed the MXU at full rate; f32 accumulation keeps the
        # long-context value sum from drifting (same precision as the scores)
        out = jnp.einsum("bgrqk,bgkd->bgrqd", probs, cv,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, hq, s, hd).astype(q.dtype)
