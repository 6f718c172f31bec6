"""Mellum — a decoder whose every feed-forward is a layer of sparse gated
experts and whose attention layers are of two kinds, as the model's own
``config.json`` gives them (JetBrains, ``model_type: mellum``).

Layer ``i`` is a ``sliding_attention`` or a ``full_attention`` layer by
``layer_types[i]``. Both are grouped-query attention with heads of a width of
their own (``head_dim``: 32 heads of 128 on a hidden size of 2,304) and rotary
positions; a sliding layer sees the last ``window`` keys and rotates by the plain
table, a full layer sees every earlier key and rotates by the YaRN table
(:func:`rotary_tables`). Then ``expert_top_k`` of ``n_experts`` gated SiLU
experts, no bias anywhere, RMSNorm, an untied head.

:class:`Mellum` is a :class:`~dsml_tpu.models.stack.LayerStack` (the walk over
unlike layers, shared with ``models/deepseek_v3.py``) and adds only what
differs: the parameter tree, the two rotary tables, the window handed to the
flash kernels and the expert layer (``models/experts.py``, mounted where
``Llama._ffn`` mounts its expert layer). The norm, the grouped-query projections (``_qkv_gqa``: key-value heads
repeated to the query heads), the embedding, the chunked loss head over
``lm_head`` and the loss are the parents' code.

Training only, dp, fsdp and one chip: ``tp``, ``sp`` / ``cp`` and ``pp`` raise,
as do the serving entry points (a windowed paged cache is ROADMAP Reach 3), and
the window lives in the flash kernels alone, so ``attn_impl`` is ``"flash"``.
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

__all__ = ["MellumConfig", "Mellum", "rotary_tables"]

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """Mellum2-12B-A2.5B's sizes by default, under the program's names."""

    vocab_size: int = 98304
    max_seq: int = 131072
    n_layer: int = 28
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    d_model: int = 2304
    d_ff: int = 896          # one expert's width (`moe_intermediate_size`)
    n_experts: int = 64      # `Llama._ffn` reads it: every layer's feed-forward is the expert layer
    expert_top_k: int = 8
    experts_held: tuple[int, int] | None = None  # (first, count): this chip's share of each layer; None = all
    expert_tile: int = 512   # rows a step of the grouped matmuls works, each expert padded to it (at 256 time follows the routing)
    layer_types: tuple[str, ...] = _PERIOD * 7
    window: int = 1024       # keys a sliding layer's query sees, its own among them
    rope_theta: float = 500000.0
    yarn_factor: float = 16.0
    yarn_original_max: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    rms_eps: float = 1e-6
    dtype: str = "float32"
    remat: bool = False      # True recomputes each block in the backward, but for `stack.KEPT` (routing, flash outputs)
    xent_chunk: int = 8192   # the blocked head's vocabulary threshold, 0 = dense (`GPT2Config.xent_chunk`)

    def __post_init__(self):
        unknown = set(self.layer_types) - set(_PERIOD)
        if unknown or len(self.layer_types) != self.n_layer:
            raise ValueError(f"layer_types must name {self.n_layer} layers of {sorted(set(_PERIOD))}; "
                             f"got {len(self.layer_types)} with {sorted(unknown)}")

    @property
    def n_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.n_experts

    @staticmethod
    def tiny(vocab_size: int = 512, remat: bool = False, experts_held=None) -> "MellumConfig":
        """Test-sized: one period of four layers, four query heads of 32 on two
        key-value heads (4 x 32 is not the hidden size), 8 experts of which a
        token takes 2, a window shorter than the sequence."""
        return MellumConfig(
            vocab_size=vocab_size, max_seq=128, n_layer=4, n_head=4, n_kv_head=2, head_dim=32,
            d_model=64, d_ff=32, n_experts=8, expert_top_k=2, experts_held=experts_held, expert_tile=16,
            layer_types=_PERIOD, window=24, yarn_original_max=32, remat=remat,
        )


def rotary_tables(cfg: MellumConfig, positions) -> dict:
    """``{layer type: (cos, sin)}``, each ``[len(positions), head_dim / 2]``
    float32: functions of the position alone, made once a step.

    ``sliding_attention``: ``inv_freq_m = theta^(-2m / head_dim)``.
    ``full_attention``: YaRN. With ``dim(r) = head_dim · ln(original_max / (2πr))
    / (2 ln theta)``, ``low = floor(dim(beta_fast))`` and ``high =
    ceil(dim(beta_slow))`` clipped to ``[0, head_dim - 1]``, frequency ``m``
    keeps its own value below ``low`` (it turns often inside the original
    context: extrapolated), is divided by ``factor`` above ``high``
    (interpolated), and is blended linearly between; ``cos`` and ``sin`` are
    both multiplied by ``attention_factor``."""
    half = cfg.head_dim // 2
    m = np.arange(half, dtype=np.float64)
    base = cfg.rope_theta ** (-2.0 * m / cfg.head_dim)

    def dim(rotations: float) -> float:
        return (cfg.head_dim * math.log(cfg.yarn_original_max / (2 * math.pi * rotations))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim(cfg.yarn_beta_slow)), cfg.head_dim - 1)
    ramp = np.clip((m - low) / max(high - low, 1e-3), 0.0, 1.0)
    yarn = (1.0 - ramp) * base + ramp * base / cfg.yarn_factor

    def table(inv_freq, scale):
        angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
        return jnp.cos(angles) * scale, jnp.sin(angles) * scale

    return {"sliding_attention": table(base, 1.0),
            "full_attention": table(yarn, cfg.yarn_attention_factor)}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _draw_layer(key, cfg: MellumConfig) -> dict:
    """One layer's leaves, drawn on the device: 0.02 normal, the residual-path
    projections (``wo``, ``w_down``) scaled by ``1 / sqrt(2 n_layer)`` as in
    ``GPT2.init``."""
    dt = jnp.dtype(cfg.dtype)
    d, f, q_d, kv_d = cfg.d_model, cfg.d_ff, cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    res_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    keys = iter(jax.random.split(key, 8))

    def normal(*shape, std=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dt)

    return {
        "rms_1": {"scale": jnp.ones(d, dt)},
        "rms_2": {"scale": jnp.ones(d, dt)},
        "attn": {"wq": normal(d, q_d), "wk": normal(d, kv_d), "wv": normal(d, kv_d),
                 "wo": normal(q_d, d, std=res_std)},
        "moe": {"router": normal(d, cfg.n_experts),
                "w_gate": normal(cfg.n_held, d, f), "w_up": normal(cfg.n_held, d, f),
                "w_down": normal(cfg.n_held, f, d, std=res_std)},
    }


@functools.partial(jax.jit, static_argnames=("cfg",))
def _draw_table(key, cfg: MellumConfig):
    return (jax.random.normal(key, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02).astype(cfg.dtype)


class Mellum(LayerStack):
    """Mellum on the Llama / GPT-2 mesh scaffolding (see module docstring)."""

    def __init__(self, config: MellumConfig | None = None):
        self.config = config or MellumConfig()

    # ---- params ---------------------------------------------------------------

    def init(self, seed: int = 0) -> dict:
        cfg = self.config
        key = jax.random.key(seed)
        return {
            "wte": _draw_table(jax.random.fold_in(key, cfg.n_layer), cfg),
            "lm_head": _draw_table(jax.random.fold_in(key, cfg.n_layer + 1), cfg),
            "rms_f": {"scale": jnp.ones(cfg.d_model, cfg.dtype)},
            "layers": [_draw_layer(jax.random.fold_in(key, i), cfg) for i in range(cfg.n_layer)],
        }

    # ---- architecture ---------------------------------------------------------

    @jax.named_scope("rope")
    def _rotate(self, t, table, head_axis=1):
        """Rotate-half by the layer's own ``(cos, sin)`` table, which
        ``_qkv_gqa`` hands through where ``Llama`` hands positions."""
        cos, sin = (jnp.expand_dims(c, head_axis - 1) for c in table)  # one angle for every head
        half = t.shape[-1] // 2
        t32 = t.astype(jnp.float32)
        t1, t2 = t32[..., :half], t32[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1).astype(t.dtype)

    def _kinds(self):
        return self.config.layer_types

    def _tables(self, positions):
        return rotary_tables(self.config, positions)

    def _check_axes(self, tp_axis, sp_axis, attn_impl):
        sharded = self._sharded(tp_axis, sp_axis)
        if sharded:
            raise NotImplementedError(
                f"Mellum: neither the expert layer nor the window is sharded over {sharded}: an "
                "exchange of rows between chips is ROADMAP Reach 2 (dp and fsdp work)")
        if attn_impl not in self._FLASH_IMPLS:
            raise NotImplementedError(
                f"Mellum: attn_impl={attn_impl!r} has no window; the flash kernels do (attn_impl='flash')")

    def _feed_forward(self, layer, h, kind: str):
        return self._ffn(layer, h)

    def _attention(self, layer, h, table, kind: str):
        from dsml_tpu.ops.flash import flash_attention

        cfg = self.config
        window = cfg.window if kind == "sliding_attention" else None
        x = _rms_norm(h, layer["rms_1"]["scale"], cfg.rms_eps)
        q, _, _, ka, va = self._qkv_gqa(layer, x, cfg.n_head, cfg.n_kv_head, table)
        # by this name the trace tells the window layers' flash calls from the full layers'
        with jax.named_scope("attn_window" if window else "attn_full"):
            out = flash_attention(q, ka, va, causal=True, window=window)
        return qmatmul(self._merge_heads(out), layer["attn"]["wo"], out.dtype)

    def _moe_block(self, moe, x, tp_axis):
        cfg = self.config
        y = expert_layer(moe, x.reshape(-1, x.shape[-1]), top_k=cfg.expert_top_k, tile=cfg.expert_tile,
                         experts_held=cfg.experts_held)
        return y.reshape(x.shape)


no_serving(Mellum, "serving needs the window in the paged cache and a cache budget by layer type: ROADMAP Reach 3")
