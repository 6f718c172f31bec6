"""DeepSeek-V3 as ``model_type: deepseek_v3`` configurations give it (Kanana-2
30B-A3B's numbers by default): latent attention, a leading dense layer, then
layers whose feed-forward is a sigmoid-routed expert layer beside shared
experts.

Block: ``h += MLA(RMSNorm(h))``, then ``h += FFN(RMSNorm(h))``; a final
RMSNorm and an untied head, no bias anywhere.

- **Latent attention** (MLA) without a query latent: ``q = x·Wq`` is
  ``n_head`` heads of ``qk_nope_dim + qk_rope_dim`` (128 + 64); ``[c, k_pe] =
  x·Wkv_a`` is a ``kv_lora_rank``-wide latent (512) and ONE 64-wide rotary key
  shared by every head; ``c`` is normalised (RMSNorm) and ``[k_nope, v] =
  c·Wkv_b`` gives each head a 128-wide key part and a ``v_head_dim``-wide value
  (128). ``q_pe`` and ``k_pe`` are rotated by pairs ``(2i, 2i+1)``
  (``rope_interleave``, :func:`rotary_table`), ``k = [k_nope, k_pe]`` with
  ``k_pe`` broadcast to the heads, and the flash kernels take q and k at 192
  and v at 128 (``ops/flash.py``, head-major; the scale is ``192 ** -0.5``).
  The latent's projections, its norm and the broadcast carry the name
  ``mla_latent``; the rotation ``rope``.
- **Feed-forward**: layers below ``first_dense`` are a gated SiLU MLP of
  ``dense_d_ff``; every other layer is ``models/experts.py``'s expert layer
  with the sigmoid router (scores in float32, a per-expert selection bias
  ``moe.bias`` in the choice alone, the chosen scores renormalised and
  multiplied by ``routed_scaling``) plus ``n_shared_experts`` shared experts,
  one gated SiLU MLP of ``n_shared_experts · d_ff`` that every token runs
  (name ``shared_expert``). The group limit (``n_group`` = ``topk_group`` = 1)
  selects every expert and is not built.

:class:`DeepseekV3` is a :class:`~dsml_tpu.models.stack.LayerStack` (the walk
over unlike layers, shared with ``models/mellum.py``): layer types ``dense``
and ``sparse``. Training only, dp, fsdp and one chip: ``tp``, ``sp`` / ``cp``
and ``pp`` raise, as do the serving entry points (a page row that holds the
latent and the rotary key is ROADMAP Reach 4).

The selection bias is a leaf of the parameter tree, as a checkpoint holds
it, but it takes no gradient. The update DeepSeek-V3 gives it between steps
(a step of ``sign`` of each expert's load error) is not built; adamw's
decoupled decay moves it by ``lr · weight_decay`` of itself a step (3e-8 at
3e-4 and 1e-4), under half a bfloat16 ulp, so in bfloat16 it stays bit-equal.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from dsml_tpu.models.common import qmatmul
from dsml_tpu.models.experts import expert_layer
from dsml_tpu.models.llama import _rms_norm
from dsml_tpu.models.stack import LayerStack, no_serving

__all__ = ["DeepseekV3Config", "DeepseekV3", "rotary_table"]

# the selection bias is drawn from the seed at this scale: the sigmoid scores of a token's 6th and 7th
# experts lie closer than that at random weights, so it changes some tokens' choice
ROUTER_BIAS_STD = 0.01


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """Kanana-2-30B-A3B's sizes by default, under the program's names."""

    vocab_size: int = 128256
    max_seq: int = 32768
    n_layer: int = 48
    n_head: int = 32
    d_model: int = 2048
    qk_nope_dim: int = 128   # a head's key part without positions (`qk_nope_head_dim`)
    qk_rope_dim: int = 64    # its rotary part, one key shared by all heads (`qk_rope_head_dim`)
    v_head_dim: int = 128
    kv_lora_rank: int = 512  # the latent's width
    dense_d_ff: int = 6144   # the dense layers' width (`intermediate_size`)
    d_ff: int = 768          # one expert's width (`moe_intermediate_size`)
    n_experts: int = 128     # the router's outputs (`n_routed_experts`)
    expert_top_k: int = 6
    n_shared_experts: int = 2
    routed_scaling: float = 2.448
    first_dense: int = 1     # `first_k_dense_replace`
    experts_held: tuple[int, int] | None = None  # (first, count): this chip's share of each layer; None = all
    expert_tile: int = 512   # rows a step of the grouped matmuls works (models/experts.py)
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    dtype: str = "float32"
    remat: bool = False      # True recomputes each block in the backward, but for `stack.KEPT` (routing, flash outputs)
    xent_chunk: int = 8192   # the blocked head's vocabulary threshold, 0 = dense (`GPT2Config.xent_chunk`)

    @property
    def n_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.n_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def layer_types(self) -> tuple[str, ...]:
        return ("dense",) * self.first_dense + ("sparse",) * (self.n_layer - self.first_dense)

    @staticmethod
    def tiny(vocab_size: int = 512, remat: bool = False, experts_held=None) -> "DeepseekV3Config":
        """Test-sized: one dense and two expert layers, four heads whose query
        and key (16 + 8) are wider than their value (16), an 8-wide rotary key,
        a 32-wide latent, 8 experts of which a token takes 2, two shared."""
        return DeepseekV3Config(
            vocab_size=vocab_size, max_seq=128, n_layer=3, n_head=4, d_model=64, qk_nope_dim=16,
            qk_rope_dim=8, v_head_dim=16, kv_lora_rank=32, dense_d_ff=96, d_ff=32, n_experts=8,
            expert_top_k=2, experts_held=experts_held, expert_tile=16, remat=remat,
        )


def rotary_table(cfg: DeepseekV3Config, positions) -> tuple:
    """``(cos, sin)``, each ``[len(positions), qk_rope_dim]`` float32, for a
    rotation by pairs: lanes ``2i`` and ``2i + 1`` both hold pair ``i``'s
    angle ``pos · theta^(-2i / qk_rope_dim)``, and ``sin`` is negated on the
    even lane, so that ``t·cos + swap(t)·sin`` (``swap`` trades each pair's two
    lanes) is the rotation (``DeepseekV3._rotate``)."""
    inv_freq = cfg.rope_theta ** (-np.arange(0, cfg.qk_rope_dim, 2, dtype=np.float64) / cfg.qk_rope_dim)
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(np.repeat(inv_freq, 2), jnp.float32)
    sign = jnp.asarray(np.tile([-1.0, 1.0], cfg.qk_rope_dim // 2), jnp.float32)
    return jnp.cos(angles), jnp.sin(angles) * sign


@functools.partial(jax.jit, static_argnames=("cfg", "kind"))
def _draw_layer(key, cfg: DeepseekV3Config, kind: str) -> dict:
    """One layer's leaves, drawn on the device: 0.02 normal, the residual-path
    projections (``wo``, every ``w_down``) scaled by ``1 / sqrt(2 n_layer)`` as
    in ``Mellum``; the selection bias normal at ``ROUTER_BIAS_STD``."""
    dt = jnp.dtype(cfg.dtype)
    d, h = cfg.d_model, cfg.n_head
    res_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    keys = iter(jax.random.split(key, 12))

    def normal(*shape, std=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dt)

    def mlp(*lead, width):
        return {"w_gate": normal(*lead, d, width), "w_up": normal(*lead, d, width),
                "w_down": normal(*lead, width, d, std=res_std)}

    layer = {
        "rms_1": {"scale": jnp.ones(d, dt)},
        "rms_2": {"scale": jnp.ones(d, dt)},
        "attn": {"wq": normal(d, h * cfg.qk_head_dim), "wkv_a": normal(d, cfg.kv_lora_rank + cfg.qk_rope_dim),
                 "kv_norm": {"scale": jnp.ones(cfg.kv_lora_rank, dt)},
                 "wkv_b": normal(cfg.kv_lora_rank, h * (cfg.qk_nope_dim + cfg.v_head_dim)),
                 "wo": normal(h * cfg.v_head_dim, d, std=res_std)},
    }
    if kind == "dense":
        return {**layer, "mlp": mlp(width=cfg.dense_d_ff)}
    return {**layer, "moe": {"router": normal(d, cfg.n_experts), "bias": normal(cfg.n_experts, std=ROUTER_BIAS_STD),
                             **mlp(cfg.n_held, width=cfg.d_ff)},
            "shared": mlp(width=cfg.n_shared_experts * cfg.d_ff)}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _draw_table(key, cfg: DeepseekV3Config):
    return (jax.random.normal(key, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02).astype(cfg.dtype)


class DeepseekV3(LayerStack):
    """DeepSeek-V3 on the Llama / GPT-2 mesh scaffolding (see module docstring)."""

    def __init__(self, config: DeepseekV3Config | None = None):
        self.config = config or DeepseekV3Config()

    # ---- params ---------------------------------------------------------------

    def init(self, seed: int = 0) -> dict:
        cfg = self.config
        key = jax.random.key(seed)
        return {
            "wte": _draw_table(jax.random.fold_in(key, cfg.n_layer), cfg),
            "lm_head": _draw_table(jax.random.fold_in(key, cfg.n_layer + 1), cfg),
            "rms_f": {"scale": jnp.ones(cfg.d_model, cfg.dtype)},
            "layers": [_draw_layer(jax.random.fold_in(key, i), cfg, kind) for i, kind in enumerate(cfg.layer_types)],
        }

    # ---- architecture ---------------------------------------------------------

    def _kinds(self):
        return self.config.layer_types

    def _tables(self, positions):
        table = rotary_table(self.config, positions)
        return {"dense": table, "sparse": table}

    def _check_axes(self, tp_axis, sp_axis, attn_impl):
        sharded = self._sharded(tp_axis, sp_axis)
        if sharded:
            raise NotImplementedError(
                f"DeepseekV3: neither the expert layer nor latent attention is sharded over {sharded}: an "
                "exchange of rows between chips is ROADMAP Reach 2 (dp and fsdp work)")
        if attn_impl not in self._FLASH_IMPLS:
            raise NotImplementedError(
                f"DeepseekV3: attn_impl={attn_impl!r} takes one head width; the flash kernels take a "
                "query-key width apart from the value width (attn_impl='flash')")

    @jax.named_scope("rope")
    def _rotate(self, t, table):
        """``t [b, s, heads, qk_rope_dim]`` rotated by pairs ``(2i, 2i + 1)``
        (:func:`rotary_table`), in float32. Each pair's two lanes are traded
        by a product with a 0/1 matrix, exact in any dtype (one term a sum):
        lane rolls measured 39 ms a step at 8,192 tokens and 32 heads on a
        v5e (PERF.md section 6)."""
        cos, sin = (c[:, None, :] for c in table)  # one angle for every head
        lane = np.arange(t.shape[-1])
        swap = jnp.asarray(lane[:, None] == (lane ^ 1)[None, :], t.dtype)  # column j takes lane j ^ 1
        swapped = jnp.dot(t, swap, preferred_element_type=jnp.float32)
        return (t.astype(jnp.float32) * cos + swapped * sin).astype(t.dtype)

    def _attention(self, layer, h, table, kind: str):
        from dsml_tpu.ops.flash import flash_attention

        cfg, a = self.config, layer["attn"]
        nope, rope, heads = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.n_head
        x = _rms_norm(h, layer["rms_1"]["scale"], cfg.rms_eps)
        b, s, _ = x.shape
        q = qmatmul(x, a["wq"], x.dtype).reshape(b, s, heads, nope + rope)
        with jax.named_scope("mla_latent"):
            latent = qmatmul(x, a["wkv_a"], x.dtype)
            c = _rms_norm(latent[..., :cfg.kv_lora_rank], a["kv_norm"]["scale"], cfg.rms_eps)
            kv = qmatmul(c, a["wkv_b"], x.dtype).reshape(b, s, heads, nope + cfg.v_head_dim)
        q_pe = self._rotate(q[..., nope:], table)
        k_pe = self._rotate(latent[..., None, cfg.kv_lora_rank:], table)
        with jax.named_scope("mla_latent"):
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, heads, rope))], axis=-1)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        out = flash_attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, kv[..., nope:])), causal=True)
        return qmatmul(self._merge_heads(out), a["wo"], out.dtype)

    def _feed_forward(self, layer, h, kind: str):
        with jax.named_scope("mlp"):
            x = _rms_norm(h, layer["rms_2"]["scale"], self.config.rms_eps)
            if kind == "dense":
                y = self._mlp_block(layer["mlp"], x, None)
            else:
                y = self._moe_block(layer["moe"], x, None)
                with jax.named_scope("shared_expert"):
                    y = y + self._mlp_block(layer["shared"], x, None)
        return h + y

    def _moe_block(self, moe, x, tp_axis):
        cfg = self.config
        y = expert_layer(moe, x.reshape(-1, x.shape[-1]), top_k=cfg.expert_top_k, tile=cfg.expert_tile,
                         experts_held=cfg.experts_held, routed_scaling=cfg.routed_scaling)
        return y.reshape(x.shape)


no_serving(DeepseekV3, "serving needs a page row that holds the latent and the rotary key: ROADMAP Reach 4")
