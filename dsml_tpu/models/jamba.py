"""Jamba — a hybrid of Mamba-1 state-space mixers and attention mixers, the
first block stack here whose layers are not all alike (ROADMAP Design 8).

Layer ``i`` mixes with causal attention where ``i % attn_layer_period ==
attn_layer_offset`` and with a Mamba-1 selective state-space layer otherwise
(Lieber et al. 2024; Gu & Dao 2023). Every layer then runs one gated SiLU MLP.
There is no positional encoding of any kind, the output head is tied to the
embedding, and every norm is an RMSNorm, including Jamba's own three on the
scan's ``dt``, ``B`` and ``C``.

:class:`Jamba` overrides :class:`~dsml_tpu.models.llama.Llama` (itself an
override of ``GPT2``) and adds only what differs: the parameter tree, the Mamba
mixer, and a ``_block`` that picks the mixer from the layer's own parameters
(``"ssm"`` or ``"attn"``) inside the unrolled loop. The embedding, the attention
mixer (grouped-query heads repeated for ``_route_attention`` -> ``ops/flash.py``;
``_rotate`` is the identity), the gated MLP, the RMSNorm, the chunked loss head
(``_unembed_matrix`` is ``wte``) and the loss are the parents' code. The walk
over the layers is its own (``_blocks_spmd``): ``remat=True`` recomputes each
block in the backward but keeps the scan kernel's outputs.

The recurrence is ``ops/selective_scan.py``'s kernel pair: the one path, on the
chip and (interpreted) off it. Training only: dp, fsdp and one chip. The mesh
axes the mixer does not implement (``tp``, ``sp`` / ``cp``, ``pp`` over unlike
layers) raise, and so do the serving entry points: a cache of recurrent state
beside the KV cache is ROADMAP Reach 5.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from dsml_tpu.models.common import fsdp_spec_fn, qmatmul
from dsml_tpu.models.llama import Llama, _rms_norm
from dsml_tpu.ops.flash import FLASH_OUTPUTS
from dsml_tpu.ops.selective_scan import SCAN_OUTPUTS, selective_scan

__all__ = ["JambaConfig", "Jamba"]

# What whole-block recomputation keeps, because it is cheap to keep and dear to make again:
# of a Mamba layer the scan's outputs (y and the block-boundary states, 105 MB a layer at
# 8,192 tokens), of an attention layer the flash forward's out and lse (47 MB at 8,192
# tokens), so neither forward kernel runs twice a step.
_KEPT = jax.checkpoint_policies.save_only_these_names(SCAN_OUTPUTS, FLASH_OUTPUTS)


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    """AI21-Jamba2-3B's sizes by default, under the program's names."""

    vocab_size: int = 65536
    max_seq: int = 262144   # the longest sequence the source declares; no table depends on it
    n_layer: int = 28
    n_head: int = 20
    n_kv_head: int = 1
    d_model: int = 2560
    d_ff: int = 8192
    d_inner: int = 5120     # mamba_expand x d_model
    d_state: int = 16
    dt_rank: int = 160
    d_conv: int = 4
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    rms_eps: float = 1e-6
    dtype: str = "float32"
    remat: bool = False     # True recomputes each block in the backward, but for `_KEPT` (the kernels' outputs)
    xent_chunk: int = 8192  # the blocked head's vocabulary threshold, 0 = dense (`GPT2Config.xent_chunk`)
    n_experts: int = 0      # `Llama._ffn` reads it: one plain gated MLP a layer

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset

    @staticmethod
    def tiny(vocab_size: int = 512, remat: bool = False) -> "JambaConfig":
        """Test-sized: one period of four layers (attention at 1), four query
        heads on one key-value head, one 128-lane tile of channels."""
        return JambaConfig(
            vocab_size=vocab_size, max_seq=128, n_layer=4, n_head=4, n_kv_head=1, d_model=64,
            d_ff=128, d_inner=128, d_state=16, dt_rank=8, attn_layer_period=4, attn_layer_offset=1,
            remat=remat,
        )


@functools.partial(jax.jit, static_argnames=("cfg", "attention"))
def _draw_layer(key, cfg: JambaConfig, attention: bool) -> dict:
    """One layer's leaves, drawn on the device: 0.02 normal, the residual-path
    projections scaled by ``1/sqrt(2 n_layer)`` as in ``GPT2.init``; Mamba's
    published ``A_log = log(1..N)``, ``D = 1`` and ``b_dt`` the inverse
    softplus of steps log-uniform in [0.001, 0.1]."""
    dt = jnp.dtype(cfg.dtype)
    d, ff, e, n, r = cfg.d_model, cfg.d_ff, cfg.d_inner, cfg.d_state, cfg.dt_rank
    res_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    keys = iter(jax.random.split(key, 10))

    def normal(*shape, std=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dt)

    layer = {
        "rms_1": {"scale": jnp.ones(d, dt)},
        "rms_2": {"scale": jnp.ones(d, dt)},
        "mlp": {"w_gate": normal(d, ff), "w_up": normal(d, ff), "w_down": normal(ff, d, std=res_std)},
    }
    if attention:
        kv_d = cfg.n_kv_head * (d // cfg.n_head)
        layer["attn"] = {"wq": normal(d, d), "wk": normal(d, kv_d), "wv": normal(d, kv_d),
                         "wo": normal(d, d, std=res_std)}
        return layer
    steps = jnp.exp(jax.random.uniform(next(keys), (e,)) * math.log(0.1 / 0.001) + math.log(0.001))
    layer["ssm"] = {
        "w_in": normal(d, 2 * e),
        "conv_w": normal(cfg.d_conv, e),
        "conv_b": jnp.zeros(e, dt),
        "w_x": normal(e, r + 2 * n),
        "dt_norm": jnp.ones(r, dt),
        "b_norm": jnp.ones(n, dt),
        "c_norm": jnp.ones(n, dt),
        "w_dt": normal(r, e),
        "b_dt": (steps + jnp.log(-jnp.expm1(-steps))).astype(dt),
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (e, n)).astype(dt),
        "d": jnp.ones(e, dt),
        "w_out": normal(e, d, std=res_std),
    }
    return layer


@functools.partial(jax.jit, static_argnames=("cfg",))
def _draw_embedding(key, cfg: JambaConfig):
    return (jax.random.normal(key, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02).astype(cfg.dtype)


def _causal_conv(u, w, bias):
    """Depthwise causal convolution along time: ``u [b, s, E]``, ``w [kernel,
    E]``; tap ``k`` of output ``t`` reads input ``t - (kernel - 1) + k``."""
    kernel, s = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (kernel - 1, 0), (0, 0))).astype(jnp.float32)
    out = bias.astype(jnp.float32)
    for k in range(kernel):
        out = out + padded[:, k:k + s] * w[k].astype(jnp.float32)
    return out.astype(u.dtype)


class Jamba(Llama):
    """Jamba on the Llama / GPT-2 mesh scaffolding (see module docstring)."""

    def __init__(self, config: JambaConfig | None = None):
        self.config = config or JambaConfig()

    # ---- params ---------------------------------------------------------------

    def init(self, seed: int = 0) -> dict:
        cfg = self.config
        key = jax.random.key(seed)
        return {
            "wte": _draw_embedding(jax.random.fold_in(key, cfg.n_layer), cfg),
            "rms_f": {"scale": jnp.ones(cfg.d_model, cfg.dtype)},
            "layers": [_draw_layer(jax.random.fold_in(key, i), cfg, cfg.is_attention(i))
                       for i in range(cfg.n_layer)],
        }

    def param_specs(self, pp: bool = False, fsdp: int = 1) -> dict:
        """Replicated but for ZeRO sharding over ``fsdp`` (each leaf on its
        first divisible dim, ``models.common.with_fsdp``)."""
        from jax.sharding import PartitionSpec as P

        if pp:
            raise NotImplementedError(
                "Jamba: pp stacks like layers on a leading axis; this stack holds two kinds")
        shapes = jax.eval_shape(lambda: self.init(0))
        spec = fsdp_spec_fn(fsdp)
        return jax.tree.map(lambda leaf: spec(P(), *leaf.shape), shapes)

    # ---- architecture ---------------------------------------------------------

    def _unembed_matrix(self, params):
        return params["wte"]  # tied

    def _rotate(self, t, positions, head_axis=1):
        return t  # no positional encoding: order enters through the scan and the causal mask

    def _block_closure(self, tp_axis, sp_axis, attn_impl):
        sharded = {axis: lax.axis_size(axis) for axis in (tp_axis, sp_axis)
                   if axis and lax.axis_size(axis) > 1}
        if sharded:
            raise NotImplementedError(
                f"Jamba: the Mamba mixer is not sharded over {sharded}: its scan needs the whole "
                "sequence and all of a channel's projections on one chip (dp and fsdp work)")
        return super()._block_closure(tp_axis, sp_axis, attn_impl)

    def _block(self, layer, h, n_head_local, tp_axis, sp_axis, attn_impl):
        if "ssm" not in layer:
            return super()._block(layer, h, n_head_local, tp_axis, sp_axis, attn_impl)
        with jax.named_scope("ssm"):
            h = h + self._ssm_block(layer, h)
        return self._ffn(layer, h, tp_axis)

    def _ssm_block(self, layer, h):
        """The Mamba-1 mixer on ``h [b, s, d]`` (module docstring of
        ``ops/selective_scan.py`` has the recurrence)."""
        cfg, p = self.config, layer["ssm"]
        x = _rms_norm(h, layer["rms_1"]["scale"], cfg.rms_eps)
        u, z = jnp.split(qmatmul(x, p["w_in"], x.dtype), 2, axis=-1)
        with jax.named_scope("ssm_conv"):
            u = jax.nn.silu(_causal_conv(u, p["conv_w"], p["conv_b"]))
        dt, b, c = jnp.split(qmatmul(u, p["w_x"], u.dtype),
                             [cfg.dt_rank, cfg.dt_rank + cfg.d_state], axis=-1)
        dt = _rms_norm(dt, p["dt_norm"], cfg.rms_eps)
        b = _rms_norm(b, p["b_norm"], cfg.rms_eps)
        c = _rms_norm(c, p["c_norm"], cfg.rms_eps)
        delta = jax.nn.softplus(
            (qmatmul(dt, p["w_dt"], dt.dtype) + p["b_dt"]).astype(jnp.float32)).astype(u.dtype)
        a = -jnp.exp(p["a_log"].astype(jnp.float32))
        y = selective_scan(u, delta, a, b, c, p["d"])
        return qmatmul(y * jax.nn.silu(z), p["w_out"], y.dtype)

    def _blocks_spmd(self, params, tokens, tp_axis=None, sp_axis=None, attn_impl="ring",
                     seq_offset=None, pp_axis=None, n_micro=1):
        """Embedding, then the layers one after another, each by its own kind."""
        if pp_axis:
            raise NotImplementedError("Jamba: no pipeline over unlike layers (see param_specs)")
        block = self._block_closure(tp_axis, sp_axis, attn_impl)
        if self.config.remat:
            block = jax.checkpoint(block, policy=_KEPT)
        h = self._embed_spmd(params, tokens, tp_axis, sp_axis)
        for layer in params["layers"]:
            h = block(layer, h)
        return h


def _no_serving(name: str):
    def entry(self, *args, **kwargs):
        raise NotImplementedError(
            f"Jamba.{name}: serving needs a second kind of per-slot state (the scan's and the "
            "convolution's) beside the KV cache: ROADMAP Reach 5")

    entry.__name__ = name
    return entry


for _name in ("init_cache", "prefill", "prefill_chunk", "decode_step", "decode_step_slots",
              "verify_step", "init_page_pool", "prefill_chunk_paged", "decode_step_slots_paged",
              "verify_step_paged", "generate", "generate_spmd"):
    setattr(Jamba, _name, _no_serving(_name))
