"""Ouro — a looped decoder (ByteDance, ``model_type: ouro``; "Scaling Latent
Reasoning via Looped Language Models"): one stack of dense layers applied
``total_ut_steps`` times with the same parameters, an exit after every pass,
and a loss that is the expected loss over the exits under a distribution a
learned gate defines.

Block, sandwich-normed (four RMSNorms a layer, each with its own scale)::

    h = h + N2(Attn(N1(h)))      # 16 heads of 128 on 16 key-value heads, rotary by halves
    h = h + N4(MLP(N3(h)))       # gated SiLU

Recurrence: ``h⁰ = E[tokens]``, ``hᵗ = N_f(F(hᵗ⁻¹))`` for ``t = 1..T``, ``F``
the layers in order with the same parameters at every ``t`` and every pass
at positions ``0..S−1``. Exit ``t`` reads ``hᵗ`` (already normed): the
per-token loss ``ℓ_t = CE(hᵗ·W_headᵀ, y)`` and, for ``t < T``, the gate
``λ_t = σ(hᵗ·w_g + b_g)``; ``p_t = λ_t ∏_{j<t}(1 − λ_j)``, ``p_T = ∏_{j<T}(1 −
λ_j)`` (:func:`exit_log_probs`). Loss: ``mean_i [Σ_t p_t,i ℓ_t,i + β Σ_t
p_t,i log p_t,i]``, ``β = entropy_weight``.

:class:`Ouro` is a :class:`~dsml_tpu.models.stack.LayerStack`: the walk applies
the layers ``passes`` times, each pass under the name ``ut_step``, and
``_pass_end`` is ``N_f``. The four exits' heads are one sweep of
``ops/xent.py`` over the four states' rows, each row weighed by its exit's
probability held constant (name ``loss_head``); the gate, the exit
distribution, the entropy and the term that carries the gate's gradient
(``Σ (p − stop_gradient(p)) · ℓ``, with ``ℓ`` constant) carry the name
``exit_gate``. Llama's norm, projections, rotation and gated MLP are reused.

Training only, dp, fsdp and one chip: ``tp``, ``sp`` / ``cp`` and ``pp`` raise,
as do the serving entry points (ROADMAP Reach 27).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from dsml_tpu.models.common import qmatmul
from dsml_tpu.models.llama import _rms_norm
from dsml_tpu.models.stack import LayerStack, no_serving

__all__ = ["OuroConfig", "Ouro", "exit_log_probs"]


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """Ouro-2.6B's sizes by default, under the program's names."""

    vocab_size: int = 49152
    max_seq: int = 65536
    n_layer: int = 48
    n_head: int = 16
    n_kv_head: int = 16
    head_dim: int = 128
    d_model: int = 2048
    d_ff: int = 5632
    total_ut_steps: int = 4    # passes of the stack a step, each followed by an exit
    entropy_weight: float = 0.1  # β, the entropy term's weight in the loss
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    dtype: str = "float32"
    remat: bool = False        # True recomputes each block application in the backward, but for `stack.KEPT`

    @staticmethod
    def tiny(vocab_size: int = 512, remat: bool = False, total_ut_steps: int = 4) -> "OuroConfig":
        """Test-sized: two layers of four heads of 16 run four times."""
        return OuroConfig(vocab_size=vocab_size, max_seq=128, n_layer=2, n_head=4, n_kv_head=4, head_dim=16,
                          d_model=64, d_ff=96, total_ut_steps=total_ut_steps, remat=remat)


def exit_log_probs(z):
    """``log p`` ``[T, ...]`` of the exits from the gate's logits ``z``
    ``[T − 1, ...]``: ``log p_t = log σ(z_t) + Σ_{j<t} log σ(−z_j)`` for ``t <
    T`` and ``log p_T = Σ_{j<T} log σ(−z_j)``; every ``p`` sums to 1 over ``t``."""
    zeros = jnp.zeros((1,) + z.shape[1:], z.dtype)
    stay = jnp.concatenate([zeros, jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)])  # log Π_{j<t} (1 − λ_j)
    leave = jnp.concatenate([jax.nn.log_sigmoid(z), zeros])  # log λ_t, and nothing at the last exit
    return stay + leave


@functools.partial(jax.jit, static_argnames=("cfg",))
def _draw_layer(key, cfg: OuroConfig) -> dict:
    """One layer's leaves, drawn on the device: 0.02 normal, the residual-path
    projections (``wo``, ``w_down``) scaled by ``1 / sqrt(2 n_layer)`` as in
    ``Mellum``; the four norms' scales 1."""
    dt = jnp.dtype(cfg.dtype)
    d, f, q_d, kv_d = cfg.d_model, cfg.d_ff, cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    res_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    keys = iter(jax.random.split(key, 7))

    def normal(*shape, std=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dt)

    return {
        **{f"rms_{i}": {"scale": jnp.ones(d, dt)} for i in range(1, 5)},
        "attn": {"wq": normal(d, q_d), "wk": normal(d, kv_d), "wv": normal(d, kv_d),
                 "wo": normal(q_d, d, std=res_std)},
        "mlp": {"w_gate": normal(d, f), "w_up": normal(d, f), "w_down": normal(f, d, std=res_std)},
    }


@functools.partial(jax.jit, static_argnames=("cfg",))
def _draw_table(key, cfg: OuroConfig):
    return (jax.random.normal(key, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02).astype(cfg.dtype)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _draw_gate(key, cfg: OuroConfig) -> dict:
    """The exit gate: a linear map ``d_model -> 1`` (0.02 normal) and its bias (0)."""
    w = jax.random.normal(key, (cfg.d_model, 1), jnp.float32) * 0.02
    return {"w": w.astype(cfg.dtype), "b": jnp.zeros(1, cfg.dtype)}


class Ouro(LayerStack):
    """Ouro on the Llama / GPT-2 mesh scaffolding (see module docstring)."""

    def __init__(self, config: OuroConfig | None = None):
        self.config = config or OuroConfig()

    # ---- params ---------------------------------------------------------------

    def init(self, seed: int = 0) -> dict:
        cfg = self.config
        key = jax.random.key(seed)
        return {
            "wte": _draw_table(jax.random.fold_in(key, cfg.n_layer), cfg),
            "lm_head": _draw_table(jax.random.fold_in(key, cfg.n_layer + 1), cfg),
            "rms_f": {"scale": jnp.ones(cfg.d_model, cfg.dtype)},
            "exit_gate": _draw_gate(jax.random.fold_in(key, cfg.n_layer + 2), cfg),
            "layers": [_draw_layer(jax.random.fold_in(key, i), cfg) for i in range(cfg.n_layer)],
        }

    # ---- architecture ---------------------------------------------------------

    @property
    def passes(self) -> int:
        return self.config.total_ut_steps

    def _pass_end(self, params, h):
        """``N_f``: each pass's state is normed before the next pass and the exit read it."""
        return _rms_norm(h, params["rms_f"]["scale"], self.config.rms_eps)

    def _final_norm(self, params, h):
        """The walk's last state is normed already (``_pass_end``)."""
        return h

    def _kinds(self):
        return ("full_attention",) * self.config.n_layer

    def _tables(self, positions):
        return {"full_attention": positions}  # Llama's rotation takes the positions themselves

    def _check_axes(self, tp_axis, sp_axis, attn_impl):
        sharded = self._sharded(tp_axis, sp_axis)
        if sharded:
            raise NotImplementedError(
                f"Ouro: the looped stack is not sharded over {sharded} (dp and fsdp work): ROADMAP Reach 27")
        if attn_impl not in self._FLASH_IMPLS:
            raise NotImplementedError(f"Ouro: attn_impl={attn_impl!r}; the family runs the flash kernels "
                                      "(attn_impl='flash')")

    def _attention(self, layer, h, positions, kind: str):
        from dsml_tpu.ops.flash import flash_attention

        cfg = self.config
        x = _rms_norm(h, layer["rms_1"]["scale"], cfg.rms_eps)
        q, _, _, ka, va = self._qkv_gqa(layer, x, cfg.n_head, cfg.n_kv_head, positions)
        out = flash_attention(q, ka, va, causal=True)
        out = qmatmul(self._merge_heads(out), layer["attn"]["wo"], out.dtype)
        return _rms_norm(out, layer["rms_2"]["scale"], cfg.rms_eps)

    def _feed_forward(self, layer, h, kind: str):
        cfg = self.config
        with jax.named_scope("mlp"):
            y = self._mlp_block(layer["mlp"], _rms_norm(h, layer["rms_3"]["scale"], cfg.rms_eps), None)
            return h + _rms_norm(y, layer["rms_4"]["scale"], cfg.rms_eps)

    # ---- the exits and the loss -------------------------------------------------

    @jax.named_scope("exit_gate")
    def _exit_log_probs(self, params, states):
        """``log p`` ``[T, b, s]`` float32 of each token's exits, from the gate
        on every state but the last (``states`` ``[T, b, s, d]``)."""
        gate = params["exit_gate"]
        z = jnp.einsum("tbsd,d->tbs", states[:-1], gate["w"][:, 0], preferred_element_type=jnp.float32)
        return exit_log_probs(z + gate["b"][0].astype(jnp.float32))

    @jax.named_scope("loss_head")
    def _exit_losses(self, params, states, targets, weights):
        """``(Σ weights · ℓ, ℓ)`` over every exit's tokens: one sweep of the
        head over the states' rows, ``ℓ`` ``[T, b, s]`` constant."""
        from dsml_tpu.ops.xent import weighted_softmax_xent

        return weighted_softmax_xent(states, params["lm_head"], jnp.broadcast_to(targets, states.shape[:-1]), weights)

    def loss_spmd(self, params, tokens, targets, tp_axis=None, sp_axis=None, attn_impl="ring",
                  pp_axis=None, n_micro=1):
        """The expected loss over the exits plus ``β`` times ``Σ p log p``, a mean over tokens."""
        if pp_axis:
            raise NotImplementedError("Ouro: no pipeline of the looped stack: ROADMAP Reach 27")
        blocks = self._block_closure(tp_axis, sp_axis, attn_impl)
        states = jnp.asarray(self._walk(params, tokens, blocks, tp_axis=tp_axis, sp_axis=sp_axis)[0])
        n = targets.size
        log_p = self._exit_log_probs(params, states)
        p = jnp.exp(log_p)
        loss, losses = self._exit_losses(params, states, targets, lax.stop_gradient(p) / n)
        with jax.named_scope("exit_gate"):
            # the gate's gradient, Σ ∂p · ℓ, and β Σ p log p; the value of the first is 0
            return loss + (jnp.sum((p - lax.stop_gradient(p)) * losses)
                           + self.config.entropy_weight * jnp.sum(p * log_p)) / n


no_serving(Ouro, "serving needs a key-value cache for each pass and an exit rule: ROADMAP Reach 27")
