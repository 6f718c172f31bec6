"""Blocked softmax cross-entropy — full logits never materialize, and the
gradients are taken in the forward.

For a tied-embedding LM the loss ``mean(logsumexp(h·Wᵀ) − h·W[target])``
normally materializes [batch·seq, vocab] float32 logits (GPT-2-small at
batch 8 × seq 1024 × vocab 50257 is ~1.6 GB — often the single largest
tensor of the step). This computes the same value by sweeping the TOKENS in
blocks of ``rows`` over the whole vocabulary, so peak memory is [rows, V]
and a block's log-sum-exp is complete when its logits are:

- one block: ``logits = h_blk·Wᵀ``, row max, exp, row sum, target logit →
  the block's share of the mean loss;
- under differentiation (custom VJP) the same sweep goes on with the
  textbook softmax-CE gradient, ``ds = (softmax − onehot) / N``,
  ``dh_blk = ds·W`` and ``dW += dsᵀ·h_blk``: three vocabulary-wide matmuls
  a block and no second pass; the backward only scales by the cotangent.

The sweep takes a weight of each row's own, held constant, and gives each
row's loss beside the weighted sum (:func:`weighted_softmax_xent`: a loss
whose weights are learned, ``models/ouro.py``'s exits); the mean
(:func:`chunked_softmax_xent`) is it at ``1 / N`` a row.

``rows`` comes from the shapes alone (:func:`block_rows`). This is the
single-shard counterpart of the TP path's distributed-logsumexp loss
(``models/gpt2.py::loss_spmd``), which splits vocab across chips instead of
across time. Used automatically by GPT-2 when the vocab is unsharded and large.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["chunked_softmax_xent", "weighted_softmax_xent", "block_rows"]

# what the head's float32 working set may take: one block's [rows, V] logits and,
# where there are several blocks, the [V, d] dW they sum into. Blocks cost traffic
# (each reads and writes that dW) and bytes cost the step's peak, which the head
# stands on. Sized on the chip (scripts/xent_head_check.py --sweep) and by the
# cells' compiles: GPT-2's vocabulary takes 4,096 rows at d 768 (786 + 147 MiB,
# under the [N, 8192] pair a vocabulary scan held at 32,768 tokens)
_HEAD_BYTES = 1 << 30


def block_rows(n: int, v: int, d: int) -> tuple[int, int]:
    """``(n_blocks, rows)`` for ``n`` tokens of width ``d`` over a vocabulary of
    ``v``: the fewest blocks whose working set fits ``_HEAD_BYTES`` (the logits
    get an eighth of it at least), rows a multiple of 8 (the
    ``n_blocks * rows - n`` padding rows weigh nothing)."""
    if 4 * n * v <= _HEAD_BYTES:
        return 1, n
    n_blocks = -(-4 * n * v // max(_HEAD_BYTES - 4 * v * d, _HEAD_BYTES // 8))
    return n_blocks, -(-n // (8 * n_blocks)) * 8


def _vary_alike(*xs):
    """``xs``, each marked varying over every mesh axis any of them varies
    over (identity on values, and outside a ``shard_map`` that tracks them):
    a scan's carry and a custom VJP's cotangents must match their inputs' types,
    and the transpose of the mark is the ``psum`` a replicated ``wte`` is owed."""
    axes = frozenset().union(*(jax.typeof(x).vma for x in xs))
    return [lax.pcast(x, tuple(missing), to="varying") if (missing := axes - jax.typeof(x).vma) else x
            for x in xs]


def _sweep(h, wte, targets, weight, rows, grads):
    """``(Σ weight · (lse − tgt), each row's own loss lse − tgt [n])`` over the
    ``n`` rows of ``h`` (``weight`` [n] float32, the losses float32) and, with
    ``grads``, the gradients ``dh`` [n, d] and ``dW`` [V, d] of the sum in the
    dtypes of ``h`` and ``wte`` (``dW`` summed over the blocks in float32),
    else ``None``."""
    n, d = h.shape
    v = wte.shape[0]
    n_blocks = -(-n // rows)
    pad = n_blocks * rows - n
    blocks = (
        jnp.pad(h, ((0, pad), (0, 0))).reshape(n_blocks, rows, d),
        jnp.pad(targets, (0, pad)).reshape(n_blocks, rows),
        jnp.pad(weight, (0, pad)).reshape(n_blocks, rows),
    )

    def body(carry, block):
        loss, dw = carry
        h_b, t_b, w_b = block
        h32 = h_b.astype(jnp.float32)
        # wte is cast where it is used, so no whole-vocab f32 copy outlives a matmul
        logits = h32 @ wte.astype(jnp.float32).T  # [rows, V]
        m = logits.max(axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        tgt = jnp.take_along_axis(logits, t_b[:, None], 1)[:, 0]
        row_loss = lse - tgt
        loss = loss + jnp.sum(row_loss * w_b)
        if not grads:
            return (loss, dw), (None, row_loss)
        onehot = jnp.arange(v)[None, :] == t_b[:, None]
        ds = (jnp.exp(logits - lse[:, None]) - onehot) * w_b[:, None]  # [rows, V]
        return (loss, dw + ds.T @ h32), ((ds @ wte.astype(jnp.float32)).astype(h.dtype), row_loss)

    loss0, dw0 = _vary_alike(h, jnp.zeros((), jnp.float32), jnp.zeros((v, d), jnp.float32))[1:]
    (loss, dw), (dh, row_losses) = lax.scan(body, (loss0, dw0 if grads else None), blocks)
    out = (loss, row_losses.reshape(-1)[:n])
    # dW is cast here, not in the backward: the float32 [V, d] must not outlive the sweep
    return out + ((dh.reshape(n_blocks * rows, d)[:n], dw.astype(wte.dtype)) if grads else (None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _blocked_xent(h, wte, targets, weight, rows):
    """``(Σ_i weight_i · (lse_i − tgt_logit_i), the rows' own losses [N])``. h
    [N, d] (any float dtype — promoted to f32 for the reductions), wte [V, d],
    targets [N] int32, weight [N] f32; the weights and the rows' losses are
    constants to differentiation."""
    return _sweep(h, wte, targets, weight, rows, grads=False)[:2]


def _fwd_rule(h, wte, targets, weight, rows):
    loss, row_losses, dh, dw = _sweep(h, wte, targets, weight, rows, grads=True)
    return (loss, row_losses), (dh, dw)


def _bwd_rule(rows, res, g):  # g: the cotangents of the loss and of the rows' losses (a constant)
    dh, dw = res
    return (g[0] * dh).astype(dh.dtype), (g[0] * dw).astype(dw.dtype), None, None


_blocked_xent.defvjp(_fwd_rule, _bwd_rule)


def chunked_softmax_xent(
    h: jax.Array,  # [..., d] final hidden states
    wte: jax.Array,  # [V, d] (tied) unembedding matrix
    targets: jax.Array,  # [...] int32
) -> jax.Array:
    """Mean next-token cross-entropy of ``h @ wte.T`` vs ``targets`` without
    ever materializing the logits. Differentiable in h and wte."""
    n = math.prod(targets.shape)
    return weighted_softmax_xent(h, wte, targets, jnp.full(targets.shape, 1.0 / n, jnp.float32))[0]


def weighted_softmax_xent(
    h: jax.Array,  # [..., d] final hidden states
    wte: jax.Array,  # [V, d] unembedding matrix
    targets: jax.Array,  # [...] int32
    weights: jax.Array,  # [...] each row's weight in the loss
) -> tuple[jax.Array, jax.Array]:
    """``(Σ weights · CE, CE)``: the weighted sum of each row's next-token
    cross-entropy, differentiable in ``h`` and ``wte`` with the weights held
    constant (the gradients are made in the same one sweep), and each row's
    own loss, shaped like ``targets``, float32 and constant. A weight that is
    itself a function of parameters, ``p``, gets its gradient from
    ``Σ (p − stop_gradient(p)) · CE`` beside this sum: together they are
    ``Σ p · CE`` with the exact gradient."""
    n, d = math.prod(h.shape[:-1]), h.shape[-1]
    operands = _vary_alike(h.reshape(n, d), wte, targets.reshape(n).astype(jnp.int32),
                           lax.stop_gradient(weights.reshape(n).astype(jnp.float32)))
    loss, row_losses = _blocked_xent(*operands, block_rows(n, wte.shape[0], d)[1])
    return loss, lax.stop_gradient(row_losses).reshape(targets.shape)
