"""Jamba as published (Lieber et al. 2024, "Jamba: A Hybrid Transformer-Mamba
Language Model"; ai21labs ``modeling_jamba``, ``model_type: jamba``): token
embedding with no positional encoding of any kind, pre-RMSNorm blocks whose
mixer is causal softmax attention where ``i % attn_layer_period ==
attn_layer_offset`` and a Mamba-1 selective state-space mixer (Gu & Dao 2023)
otherwise, each followed by a gated SiLU MLP (``num_experts`` 1: no routing), a
final RMSNorm, the output head tied to the embedding, mean next-token cross
entropy. Plain ``jax.numpy`` in float32 at ``default_matmul_precision
("highest")``: no kernels, no cache, no batching (one row at a time), nothing
imported from the program.

The Mamba mixer, for ``x [seq, d]``, inner width ``E``, state ``N``, rank ``R``:
``[u, z] = x W_in``; ``u = silu(causal depthwise conv1d(u) + b_conv)``;
``[dt, B, C] = u W_x``, each RMS-normalised with a learned scale (Jamba's own
step); ``delta = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; the scan
``s_t = exp(delta_t A) * s_{t-1} + delta_t u_t B_t``, ``y_t = C_t . s_t + D u_t``
from ``s_0 = 0``, as a sequential ``lax.scan`` over time; ``out = (y * silu(z))
W_out``.

It reads the program's parameter tree (a layer holds ``ssm`` or ``attn``; the
convolution's weight is ``[kernel, E]``, tap ``k`` multiplying ``u[t - (kernel -
1) + k]``) and casts one layer's leaves to float32 at a time, so both sides hold
the same bfloat16 weights and differ only in how they compute.

Departures from the source: none in the mathematics. The depth and, where the
configuration says so, the vocabulary are cut (the configuration file lists
both); attention scores are computed in query blocks of ``QUERY_BLOCK`` rows,
the head's log-sum-exp in vocabulary chunks of ``VOCAB_CHUNK`` rows, and the
scan's backward keeps the state at every ``SCAN_CHUNK``-th step and recomputes
between, which changes memory and not arithmetic.

``precision`` (``"float32"`` by default) is the type the scan computes in, its
state, its inputs and its backward. The lower ones (``"bfloat16"``, ``"float16"``,
``"float8_e4m3fn"``) are the controls that the limits in the traffic file are set
against (PERF.md §4): computed so, the reference itself has to come out as not
correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 1024
VOCAB_CHUNK = 8192
SCAN_CHUNK = 256


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _attend(q, k, v, start):
    """Queries ``start ...`` of one row against the keys they may see."""
    end = start + q.shape[2]
    scores = jnp.einsum("gjqd,gkd->gjqk", q, k) / np.sqrt(q.shape[-1])
    visible = jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]
    return jnp.einsum("gjqk,gkd->gjqd", jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1), v)


def _attention(q, k, v):
    """Causal softmax attention for one row: q ``[heads, seq, head_dim]``, k and
    v ``[kv_heads, seq, head_dim]``, query head ``h`` reading key-value head
    ``h // (heads // kv_heads)``. The backward computes a query block's scores
    again and keeps none."""
    heads, seq, head_dim = q.shape
    q = q.reshape(k.shape[0], heads // k.shape[0], seq, head_dim)
    attend = jax.checkpoint(_attend, static_argnums=3)
    out = [attend(q[:, :, start:start + QUERY_BLOCK], k[:, :start + QUERY_BLOCK],
                  v[:, :start + QUERY_BLOCK], start) for start in range(0, seq, QUERY_BLOCK)]
    return jnp.concatenate(out, axis=2).reshape(heads, seq, head_dim)


def _attention_mixer(p, x, *, n_head, n_kv_head):
    seq, d = x.shape
    head_dim = d // n_head

    def heads(t, n):
        return t.reshape(seq, n, head_dim).transpose(1, 0, 2)

    a = _attention(heads(x @ p["wq"], n_head), heads(x @ p["wk"], n_kv_head),
                   heads(x @ p["wv"], n_kv_head))
    return a.transpose(1, 0, 2).reshape(seq, d) @ p["wo"]


def selective_scan(u, delta, a, b, c, d, dtype=jnp.float32):
    """The recurrence alone, one row: u, delta ``[seq, E]``, a ``[E, N]``, b, c
    ``[seq, N]``, d ``[E]`` -> y ``[seq, E]``. Time is a sequential ``lax.scan``."""
    u, delta, a, b, c, d = (t.astype(dtype) for t in (u, delta, a, b, c, d))

    def step(state, inputs):
        u_t, delta_t, b_t, c_t = inputs
        state = jnp.exp(delta_t[:, None] * a) * state + (delta_t * u_t)[:, None] * b_t[None, :]
        return state, state @ c_t + d * u_t

    @jax.checkpoint
    def chunk(state, inputs):
        return jax.lax.scan(step, state, inputs)

    seq = u.shape[0]
    steps = math.gcd(seq, SCAN_CHUNK)
    chunks = jax.tree.map(lambda t: t.reshape(seq // steps, steps, *t.shape[1:]), (u, delta, b, c))
    _, y = jax.lax.scan(chunk, jnp.zeros(a.shape, dtype), chunks)
    return y.reshape(seq, -1)


def causal_depthwise_conv(u, w):
    """``u [seq, E]``, ``w [kernel, E]``: one filter of ``kernel`` taps a channel;
    tap ``k`` of output ``t`` reads input ``t - (kernel - 1) + k``, zero before
    the row starts. As a sum over the ``kernel`` windows of the padded row (the
    TPU compiler refuses the weight gradient of a 5,120-group
    ``conv_general_dilated``; the tests hold this to that operator)."""
    kernel, seq = w.shape[0], u.shape[0]
    padded = jnp.pad(u, ((kernel - 1, 0), (0, 0)))
    windows = jnp.stack([padded[k:k + seq] for k in range(kernel)])
    return jnp.einsum("kse,ke->se", windows, w)


def _mamba_mixer(p, x, *, eps, dtype):
    rank, state = p["dt_norm"].shape[0], p["b_norm"].shape[0]
    u, z = jnp.split(x @ p["w_in"], 2, axis=-1)
    u = jax.nn.silu(causal_depthwise_conv(u, p["conv_w"]) + p["conv_b"])
    dt, b, c = jnp.split(u @ p["w_x"], [rank, rank + state], axis=-1)
    dt, b, c = (_rms_norm(t, p[name], eps) for t, name in ((dt, "dt_norm"), (b, "b_norm"), (c, "c_norm")))
    delta = jax.nn.softplus(dt @ p["w_dt"] + p["b_dt"])
    y = selective_scan(u, delta, -jnp.exp(p["a_log"]), b, c, p["d"], dtype).astype(jnp.float32)
    return (y * jax.nn.silu(z)) @ p["w_out"]


def block(layer, h, *, n_head, n_kv_head, eps, precision="float32"):
    """One block on one row ``h [seq, d]``: the mixer the layer's own
    parameters name, then the gated MLP, each on an RMS-normalised input and
    added to the residual stream."""
    layer = _f32(layer)
    x = _rms_norm(h, layer["rms_1"]["scale"], eps)
    if "ssm" in layer:
        h = h + _mamba_mixer(layer["ssm"], x, eps=eps, dtype=jnp.dtype(precision))
    else:
        h = h + _attention_mixer(layer["attn"], x, n_head=n_head, n_kv_head=n_kv_head)
    x = _rms_norm(h, layer["rms_2"]["scale"], eps)
    mlp = layer["mlp"]
    return h + (jax.nn.silu(x @ mlp["w_gate"]) * (x @ mlp["w_up"])) @ mlp["w_down"]


def head_nll(rms_f, wte, h, targets, *, eps):
    """Summed next-token negative log likelihood of one row, the tied head's
    log-sum-exp taken over vocabulary chunks."""
    x = _rms_norm(h, rms_f["scale"].astype(jnp.float32), eps)
    lse = jnp.full(h.shape[0], -jnp.inf)
    for start in range(0, wte.shape[0], VOCAB_CHUNK):
        logits = x @ wte[start:start + VOCAB_CHUNK].astype(jnp.float32).T
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1))
    target_logit = jnp.einsum("sd,sd->s", x, wte[targets].astype(jnp.float32))
    return (lse - target_logit).sum()


def _sizes(layer, sizes: dict) -> dict:
    """``block``'s keywords for ``layer``: the scan's precision is no part of an
    attention block's program, which then compiles once."""
    return sizes if "ssm" in layer else {**sizes, "precision": "float32"}


def _mean_loss(block_fn, head_fn, params, tokens, targets, *, eps, **sizes):
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for row_tokens, row_targets in zip(tokens, targets):
            h = params["wte"][row_tokens].astype(jnp.float32)
            for layer in params["layers"]:
                h = block_fn(layer, h, eps=eps, **_sizes(layer, sizes))
            total = total + head_fn(params["rms_f"], params["wte"], h, row_targets, eps=eps)
    return total / tokens.size


def loss_fn(params, tokens, targets, *, n_head, n_kv_head, eps, precision="float32"):
    """Mean loss over ``tokens`` / ``targets`` ``[rows, seq]``, one traceable
    function: what ``jax.grad`` differentiates in the tests."""
    return _mean_loss(block, head_nll, params, tokens, targets, n_head=n_head, n_kv_head=n_kv_head,
                      eps=eps, precision=precision)


_STATIC = ("n_head", "n_kv_head", "eps", "precision")
_block_jit = jax.jit(block, static_argnames=_STATIC)
_head_jit = jax.jit(head_nll, static_argnames=("eps",))
_head_grad_jit = jax.jit(jax.value_and_grad(head_nll, argnums=2), static_argnames=("eps",))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _block_pull(layer, h, dh, **sizes):
    """The cotangent of one block's input alone."""
    return jax.vjp(lambda x: block(layer, x, **sizes), h)[1](dh)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def _block_pull_leaves(layer, h, dh, **sizes):
    """The cotangents of one block's leaves (float32) and of its input."""
    return jax.vjp(lambda p, x: block(p, x, **sizes), _f32(layer), h)[1](dh)


def loss(params, tokens, targets, *, n_head, n_kv_head, eps, precision="float32") -> float:
    """The same number for host int arrays at the published widths: each kind
    of block is one jitted program called per layer and per row, one layer's
    weights in float32 at a time, so the reference fits beside the trained
    state and costs two blocks' compiles whatever the depth."""
    return float(_mean_loss(_block_jit, _head_jit, params, np.asarray(tokens), np.asarray(targets),
                            n_head=n_head, n_kv_head=n_kv_head, eps=eps, precision=precision))


def layer_grads(params, tokens, targets, layers, *, n_head, n_kv_head, eps, precision="float32") -> dict:
    """``{i: the float32 gradient of the mean loss by the leaves of
    params["layers"][i]}`` for ``i`` in ``layers``, at the published widths
    beside the trained state: the forward keeps each block's input, the
    backward pulls the cotangent down one block at a time (one jitted program a
    kind of block, one block's residuals alive at a time) and stops at the
    lowest layer asked for."""
    sizes = dict(n_head=n_head, n_kv_head=n_kv_head, eps=eps, precision=precision)
    grads: dict = {}
    with jax.default_matmul_precision("highest"):
        for row_tokens, row_targets in zip(np.asarray(tokens), np.asarray(targets)):
            inputs = [params["wte"][row_tokens].astype(jnp.float32)]
            for layer in params["layers"]:
                inputs.append(_block_jit(layer, inputs[-1], **_sizes(layer, sizes)))
            _, dh = _head_grad_jit(params["rms_f"], params["wte"], inputs.pop(), row_targets, eps=eps)
            for i in reversed(range(min(layers), len(params["layers"]))):
                layer = params["layers"][i]
                if i in layers:
                    leaves, dh = _block_pull_leaves(layer, inputs.pop(), dh, **_sizes(layer, sizes))
                    grads[i] = leaves if i not in grads else jax.tree.map(jnp.add, grads[i], leaves)
                else:
                    dh = _block_pull(layer, inputs.pop(), dh, **_sizes(layer, sizes))
    return jax.tree.map(lambda g: g / np.asarray(tokens).size, grads)
