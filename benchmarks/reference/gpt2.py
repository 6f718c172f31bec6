"""GPT-2 as published (Radford et al. 2019; openai-community/gpt2
``modeling_gpt2``): learned token and position embeddings, pre-LayerNorm
blocks of causal multi-head attention and a 4x GELU (tanh form, ``gelu_new``)
MLP, a final LayerNorm, the output head tied to the token embedding, mean
next-token cross entropy. Plain ``jax.numpy`` in float32 at
``default_matmul_precision("highest")``: no kernels, no cache, no batching
(one row at a time), nothing imported from the program.

It reads the program's parameter tree (``wqkv`` is ``[d, 3, d]``: slot 0/1/2 =
q/k/v) and casts each leaf to float32, so both sides hold the same bfloat16
weights and differ only in how they compute.

Departures from the source: no dropout (the program has none); attention
scores are computed in query blocks of ``QUERY_BLOCK`` rows against the keys
up to the block's end, which changes memory and not arithmetic.

TOLERANCE. ``reference_tolerance_nats`` in a traffic file bounds
|program step-1 loss - reference loss| on the whole first batch. The program
computes in bfloat16 (8 bits of mantissa) with float32 accumulation; on the
v5e its loss sat within 4.5e-4 nats of this reference in all 62 runs of the
four cells (PERF.md §6, PR 23), so the bound is 1e-2. An fp8 or int8 matmul path rounds 16 to 32 times
coarser per product and lands an order of magnitude outside it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 1024


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x**3)))


def _attention(q, k, v):
    """Causal softmax attention for one row: q, k, v ``[heads, seq, head_dim]``."""
    seq, head_dim = q.shape[1], q.shape[2]
    out = []
    for start in range(0, seq, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, seq)
        scores = jnp.einsum("hqd,hkd->hqk", q[:, start:end], k[:, :end]) / np.sqrt(head_dim)
        visible = jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]
        scores = jnp.where(visible[None], scores, -jnp.inf)
        out.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v[:, :end]))
    return jnp.concatenate(out, axis=1)


@jax.jit
def _embed(wte, wpe, tokens):
    return wte.astype(jnp.float32)[tokens] + wpe.astype(jnp.float32)[: tokens.shape[0]]


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _block(layer, h, *, n_head, eps):
    """One pre-LN block on one row ``h [seq, d]``."""
    layer = _f32(layer)
    seq, d = h.shape
    x = _layer_norm(h, layer["ln_1"]["scale"], layer["ln_1"]["bias"], eps)
    qkv = jnp.einsum("sd,dce->sce", x, layer["attn"]["wqkv"]) + layer["attn"]["bqkv"]
    q, k, v = (qkv[:, i].reshape(seq, n_head, d // n_head).transpose(1, 0, 2) for i in range(3))
    a = _attention(q, k, v).transpose(1, 0, 2).reshape(seq, d)
    h = h + a @ layer["attn"]["wo"] + layer["attn"]["bo"]
    x = _layer_norm(h, layer["ln_2"]["scale"], layer["ln_2"]["bias"], eps)
    m = _gelu_new(x @ layer["mlp"]["w_in"] + layer["mlp"]["b_in"])
    return h + m @ layer["mlp"]["w_out"] + layer["mlp"]["b_out"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_nll(ln_f, wte, h, targets, *, eps):
    """Summed next-token negative log likelihood of one row."""
    ln_f = _f32(ln_f)
    logits = _layer_norm(h, ln_f["scale"], ln_f["bias"], eps) @ wte.astype(jnp.float32).T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()


def loss(params, tokens, targets, *, n_head: int, eps: float) -> float:
    """Mean loss over ``tokens``/``targets`` ``[rows, seq]`` (host int arrays).
    Each block is one small jitted program called per layer and per row, so
    the reference costs one block's compile whatever the depth."""
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for row_tokens, row_targets in zip(np.asarray(tokens), np.asarray(targets)):
            h = _embed(params["wte"], params["wpe"], row_tokens)
            for layer in params["layers"]:
                h = _block(layer, h, n_head=n_head, eps=eps)
            total += float(_head_nll(params["ln_f"], params["wte"], h, row_targets, eps=eps))
    return total / np.asarray(tokens).size
