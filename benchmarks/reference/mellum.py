"""Mellum as its ``config.json`` gives it (JetBrains, ``model_type: mellum``,
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
token embedding, pre-RMSNorm blocks ``h += Attn_i(RMSNorm(h))``, ``h +=
MoE(RMSNorm(h))``, a final RMSNorm, an untied head, mean next-token cross
entropy; no bias anywhere. Plain ``jax.numpy`` in float32 at
``default_matmul_precision("highest")``: no kernels, no sort, no grouped product,
no cache, no batching (one row at a time), nothing imported from the program.

Attention of layer ``i``: 32 query heads of ``head_dim`` on 4 key-value heads
(query head ``j`` reads key-value head ``j // 8``), rotary (rotate-half) on ``q``
and ``k``, scores ``q·kᵀ / sqrt(head_dim)`` under a dense mask built from ``i -
j``: query ``i`` sees key ``j`` iff ``0 <= i - j`` in a ``full_attention`` layer
and iff ``0 <= i - j < sliding_window`` in a ``sliding_attention`` layer; softmax
in float32. Sliding layers rotate by ``inv_freq_m = theta^(-2m / head_dim)``; full
layers by YaRN as ``rope_parameters.full_attention`` gives it (:func:`inv_freq`),
``cos`` and ``sin`` multiplied by ``attention_factor``.

Experts: ``p = softmax(x·Wr)`` over all experts in float32, the ``top_k``
largest, ``w = p_top / Σ p_top`` (``norm_topk_prob``), ``y = Σ_e w_e · (SiLU(x·Wg_e)
⊙ (x·Wu_e))·Wd_e``. EVERY expert held is evaluated for EVERY token and weighted by
a ``[tokens, experts]`` matrix that is zero off the top ``top_k``.
``experts_held = (first, count)`` is the chip's share of the deployment: the
router and the top-k are over all experts, the sum over the held ones (whose
matrices are the leaves' leading axis).

It reads the program's parameter tree and casts a layer's leaves to float32 as
it uses them (an expert at a time), so both sides hold the same bfloat16 weights
and differ only in how they compute.

Departures from the source, each also an ``assumed`` entry of the configuration
file: none in the mathematics the config shows. The config has no key for a
per-head query/key norm, a multi-token-prediction head or an auxiliary router
loss, so there is none here; ``intermediate_size`` selects nothing (every
``mlp_layer_types`` entry is ``sparse``). The depth and the vocabulary are cut
(the configuration file lists both). Scores are computed in query blocks of
``QUERY_BLOCK`` rows (each against every key), the experts over ``TOKEN_CHUNK`` tokens at a time with each
expert's step recomputed in the backward, the head's log-sum-exp in vocabulary
chunks of ``VOCAB_CHUNK`` rows: memory, not arithmetic.

``variant`` (``"float32"`` by default) names a deliberate fault, the controls
that the limits in the traffic file are set against (PERF.md §4): computed so,
the reference itself has to come out as not correct. ``experts_float8``: the
experts' matmuls on operands rounded to float8 (e4m3), the nearest precision
below the configuration's bfloat16 operands; ``experts_bfloat16``: the
experts' matmuls on bfloat16 operands with a bfloat16 accumulator (rounded
after every 128 terms); ``router_bfloat16``: the router's logits and softmax in
bfloat16; ``window_plus_one`` / ``window_absent``; ``no_renormalisation``: ``w =
p_top``; ``plain_rotary``: full layers rotate by the sliding layers' table.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
TOKEN_CHUNK = 1024
VOCAB_CHUNK = 8192
VARIANTS = ("float32", "experts_float8", "experts_bfloat16", "router_bfloat16", "window_plus_one", "window_absent",
            "no_renormalisation", "plain_rotary")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the equations need beside the parameters, under the source's names."""

    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts_per_tok: int
    sliding_window: int
    layer_types: tuple
    rms_norm_eps: float
    rope_theta: float
    yarn_factor: float
    yarn_original_max: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_attention_factor: float
    experts_held: tuple | None = None


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def inv_freq(s: Sizes, kind: str) -> np.ndarray:
    """The ``head_dim / 2`` rotary frequencies of a layer type. Sliding:
    ``theta^(-2m / head_dim)``. Full, YaRN: with ``dim(r) = head_dim ·
    ln(original_max / (2πr)) / (2 ln theta)``, ``low = floor(dim(beta_fast))``,
    ``high = ceil(dim(beta_slow))`` clipped to ``[0, head_dim - 1]`` and ``ramp_m
    = clip((m - low) / (high - low), 0, 1)``: ``(1 - ramp_m) · base_m + ramp_m ·
    base_m / factor``."""
    m = np.arange(s.head_dim // 2, dtype=np.float64)
    base = s.rope_theta ** (-2.0 * m / s.head_dim)
    if kind == "sliding_attention":
        return base

    def dim(rotations):
        return s.head_dim * math.log(s.yarn_original_max / (2 * math.pi * rotations)) / (2 * math.log(s.rope_theta))

    low, high = max(math.floor(dim(s.yarn_beta_fast)), 0), min(math.ceil(dim(s.yarn_beta_slow)), s.head_dim - 1)
    ramp = np.clip((m - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * base + ramp * base / s.yarn_factor


def _rotary(seq: int, s: Sizes, kind: str, variant: str):
    """``(cos, sin) [seq, head_dim / 2]`` float32 of a layer type."""
    if variant == "plain_rotary":
        kind = "sliding_attention"
    scale = s.yarn_attention_factor if kind == "full_attention" else 1.0
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq(s, kind), jnp.float32)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rotate(t, cos, sin):
    """Rotate-half: ``t [heads, seq, head_dim]``."""
    half = t.shape[-1] // 2
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1)


def _attend(q, k, v, start, window):
    """Queries ``start ...`` of one row against every key, under the dense
    mask of ``i - j``: q ``[kv_heads, group, block, head_dim]``, k and v
    ``[kv_heads, seq, head_dim]``."""
    scores = jnp.einsum("gjqd,gkd->gjqk", q, k) / np.sqrt(q.shape[-1])
    distance = (start + jnp.arange(q.shape[2]))[:, None] - jnp.arange(k.shape[1])[None, :]
    visible = distance >= 0 if window is None else (distance >= 0) & (distance < window)
    return jnp.einsum("gjqk,gkd->gjqd", jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1), v)


def _attention(p, x, *, kind, s: Sizes, variant):
    seq = x.shape[0]
    window = s.sliding_window if kind == "sliding_attention" else None
    if window is not None and variant in ("window_plus_one", "window_absent"):
        window = window + 1 if variant == "window_plus_one" else None

    def heads(t, n):
        return t.reshape(seq, n, s.head_dim).transpose(1, 0, 2)

    cos, sin = _rotary(seq, s, kind, variant)
    q = _rotate(heads(x @ p["wq"], s.num_attention_heads), cos, sin)
    k = _rotate(heads(x @ p["wk"], s.num_key_value_heads), cos, sin)
    v = heads(x @ p["wv"], s.num_key_value_heads)
    # query blocks one after another, each block's scores computed again in the backward
    block = math.gcd(seq, QUERY_BLOCK)
    q = q.reshape(s.num_key_value_heads, -1, seq // block, block, s.head_dim).transpose(2, 0, 1, 3, 4)
    attend = jax.checkpoint(lambda _, part: (None, _attend(part[0], k, v, part[1], window)))
    out = jax.lax.scan(attend, None, (q, jnp.arange(0, seq, block)))[1]  # [blocks, kv_heads, group, block, head_dim]
    out = out.transpose(1, 2, 0, 3, 4).reshape(s.num_attention_heads, seq, s.head_dim)
    return out.transpose(1, 0, 2).reshape(seq, -1) @ p["wo"]


def routing_weights(x, w_router, *, s: Sizes, variant="float32"):
    """``[tokens, experts]`` float32: each token's renormalised probability on
    its ``top_k`` experts, zero elsewhere."""
    logits = x @ w_router
    if variant == "router_bfloat16":
        logits = (x.astype(jnp.bfloat16) @ w_router.astype(jnp.bfloat16))
    p = jax.nn.softmax(logits, axis=-1).astype(jnp.float32)
    top_p, top_e = jax.lax.top_k(p, s.num_experts_per_tok)
    if variant != "no_renormalisation":
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return (jax.nn.one_hot(top_e, p.shape[-1], dtype=jnp.float32) * top_p[..., None]).sum(1)


def _dot(a, b, variant):
    if variant == "experts_float8":  # operands of 4 exponent and 3 mantissa bits, summed in float32
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32) @ b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if variant != "experts_bfloat16":
        return a @ b
    # a bfloat16 accumulator: the partial sum is rounded after every 128 terms
    a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    total = jnp.zeros((a.shape[0], b.shape[1]), jnp.bfloat16)
    for at in range(0, a.shape[1], 128):
        part = jnp.dot(a[:, at:at + 128], b[at:at + 128], preferred_element_type=jnp.float32)
        total = (total.astype(jnp.float32) + part).astype(jnp.bfloat16)
    return total.astype(jnp.float32)


def _experts(moe, x, weight, variant):
    """``Σ_e weight[:, e] · expert_e(x)`` over the experts held, one expert
    and ``TOKEN_CHUNK`` tokens at a time; ``weight [tokens, held]``. Both loops
    are ``lax.scan``s whose steps are computed again in the backward, so the
    backward holds one chunk's and one expert's intermediates and sums the
    experts' cotangents in one buffer."""
    def one(x, y, expert):
        w_gate, w_up, w_down, w = _f32(expert)
        mid = jax.nn.silu(_dot(x, w_gate, variant)) * _dot(x, w_up, variant)
        return y + w[:, None] * _dot(mid, w_down, variant)

    @jax.checkpoint
    def chunk(_, part):
        x, weight = part
        step = jax.checkpoint(lambda y, expert: (one(x, y, expert), None))
        return None, jax.lax.scan(step, jnp.zeros_like(x), (moe["w_gate"], moe["w_up"], moe["w_down"], weight.T))[0]

    size = math.gcd(x.shape[0], TOKEN_CHUNK)
    parts = (x.reshape(-1, size, x.shape[1]), weight.reshape(-1, size, weight.shape[1]))
    return jax.lax.scan(chunk, None, parts)[1].reshape(x.shape)


def moe(p, x, *, s: Sizes, variant="float32"):
    """The expert layer on one row ``x [seq, d]``: the held experts' part."""
    weight = routing_weights(x, p["router"].astype(jnp.float32), s=s, variant=variant)
    first, count = s.experts_held or (0, weight.shape[1])
    return _experts(p, x, weight[:, first:first + count], variant)


def block(layer, h, *, kind, s: Sizes, variant="float32"):
    """One block on one row ``h [seq, d]``."""
    x = _rms_norm(h, layer["rms_1"]["scale"].astype(jnp.float32), s.rms_norm_eps)
    h = h + _attention(_f32(layer["attn"]), x, kind=kind, s=s, variant=variant)
    x = _rms_norm(h, layer["rms_2"]["scale"].astype(jnp.float32), s.rms_norm_eps)
    return h + moe(layer["moe"], x, s=s, variant=variant)


def head_nll(rms_f, lm_head, h, targets, *, eps):
    """Summed next-token negative log likelihood of one row, the untied head's
    log-sum-exp taken over vocabulary chunks."""
    x = _rms_norm(h, rms_f["scale"].astype(jnp.float32), eps)
    lse = jnp.full(h.shape[0], -jnp.inf)
    for start in range(0, lm_head.shape[0], VOCAB_CHUNK):
        logits = x @ lm_head[start:start + VOCAB_CHUNK].astype(jnp.float32).T
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1))
    target_logit = jnp.einsum("sd,sd->s", x, lm_head[targets].astype(jnp.float32))
    return (lse - target_logit).sum()


def _mean_loss(block_fn, head_fn, params, tokens, targets, *, s: Sizes, variant):
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for row_tokens, row_targets in zip(tokens, targets):
            h = params["wte"][row_tokens].astype(jnp.float32)
            for kind, layer in zip(s.layer_types, params["layers"]):
                h = block_fn(layer, h, kind=kind, s=s, variant=variant)
            total = total + head_fn(params["rms_f"], params["lm_head"], h, row_targets, eps=s.rms_norm_eps)
    return total / tokens.size


def loss_fn(params, tokens, targets, *, s: Sizes, variant="float32"):
    """Mean loss over ``tokens`` / ``targets`` ``[rows, seq]``, one traceable
    function: what ``jax.grad`` differentiates in the tests."""
    return _mean_loss(block, head_nll, params, tokens, targets, s=s, variant=variant)


_STATIC = ("kind", "s", "variant")
_block_jit = jax.jit(block, static_argnames=_STATIC)
_head_jit = jax.jit(head_nll, static_argnames=("eps",))
_head_grad_jit = jax.jit(jax.value_and_grad(head_nll, argnums=2), static_argnames=("eps",))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _block_pull(layer, h, dh, **how):
    """The cotangent of one block's input alone."""
    return jax.vjp(lambda x: block(layer, x, **how), h)[1](dh)[0]


def _by_expert(layer, experts, cut=lambda leaf, at, e: leaf[at]):
    """``layer`` with each of ``experts`` a subtree of its own under
    ``moe.experts``, its three matrices read out of the stacked leaves."""
    matrices = {name: leaf for name, leaf in layer["moe"].items() if name != "router"}
    return {**layer, "moe": {"router": layer["moe"]["router"],
                             "experts": {e: {name: cut(leaf, at, e) for name, leaf in matrices.items()}
                                         for at, e in enumerate(experts)}}}


def watched_leaves(layer, experts):
    """``layer`` (a whole layer's parameters, moments or gradients) with its
    three expert leaves cut to the ``experts`` (indices into their leading axis)
    that a comparison at the published widths can afford to hold in float32,
    each expert's three matrices a subtree of its own under ``moe.experts``."""
    return _by_expert(layer, tuple(experts), cut=lambda leaf, at, e: leaf[e])


@functools.partial(jax.jit, static_argnames=(*_STATIC, "experts", "busiest"))
def _block_pull_leaves(layer, h, dh, experts=None, busiest=0, **how):
    """The cotangents of one block's leaves (float32) and of its input, and
    the experts kept: all (``experts`` None and ``busiest`` 0: the leaves as they
    are), the ``experts`` named, or the ``busiest`` experts, those whose ``w_down``
    has the largest gradient (``[busiest, ...]`` leaves and their indices). The
    expert leaves go in as they are stored (an expert is cast up as it is used:
    a layer's are 1.6 GB in float32), so their cotangent is rounded to that type
    where it leaves the sum over a chunk of tokens; every other leaf goes in as
    float32."""
    moe = {name: leaf.astype(jnp.float32) if name == "router" else leaf for name, leaf in layer["moe"].items()}
    layer = {**_f32({name: leaves for name, leaves in layer.items() if name != "moe"}), "moe": moe}
    leaves, dh = jax.vjp(lambda p, x: block(p, x, **how), layer, h)[1](dh)
    if experts is None and not busiest:
        return _f32(leaves), None, dh
    if experts is None:
        norms = jnp.sum(jnp.square(leaves["moe"]["w_down"].astype(jnp.float32)), axis=(1, 2))
        pick = jnp.sort(jax.lax.top_k(norms, busiest)[1])
    else:
        pick = jnp.asarray(experts)
    moe = {name: leaf if name == "router" else leaf[pick] for name, leaf in leaves["moe"].items()}
    return _f32({**leaves, "moe": moe}), pick, dh


def loss(params, tokens, targets, *, s: Sizes, variant="float32") -> float:
    """The same number for host int arrays at the published widths: each type
    of block is one jitted program called per layer and per row, so the
    reference fits beside the trained state and costs two blocks' compiles
    whatever the depth."""
    return float(_mean_loss(_block_jit, _head_jit, params, np.asarray(tokens), np.asarray(targets),
                            s=s, variant=variant))


def layer_grads(params, tokens, targets, layers, *, s: Sizes, variant="float32", experts=None, busiest=0) -> dict:
    """``{i: the float32 gradient of the mean loss by the leaves of
    params["layers"][i]}`` for ``i`` in ``layers``, at the published widths
    beside the trained state: the forward keeps each block's input, the
    backward pulls the cotangent down one block at a time and stops at the
    lowest layer asked for. Of the expert leaves: all; or, in the form of
    :func:`watched_leaves`, the ``experts`` named (``{layer: indices}``) or each
    layer's ``busiest`` experts by the first row's gradient."""
    grads, kept = {}, dict(experts or {})
    with jax.default_matmul_precision("highest"):
        for row_tokens, row_targets in zip(np.asarray(tokens), np.asarray(targets)):
            inputs = [params["wte"][row_tokens].astype(jnp.float32)]
            for kind, layer in zip(s.layer_types, params["layers"]):
                inputs.append(_block_jit(layer, inputs[-1], kind=kind, s=s, variant=variant))
            _, dh = _head_grad_jit(params["rms_f"], params["lm_head"], inputs.pop(), row_targets,
                                   eps=s.rms_norm_eps)
            for i in reversed(range(min(layers), len(params["layers"]))):
                how = dict(kind=s.layer_types[i], s=s, variant=variant)
                if i in layers:
                    leaves, pick, dh = _block_pull_leaves(params["layers"][i], inputs.pop(), dh, busiest=busiest,
                                                          experts=kept.get(i), **how)
                    if pick is not None:
                        kept[i] = tuple(int(e) for e in np.asarray(pick))
                    grads[i] = leaves if i not in grads else jax.tree.map(jnp.add, grads[i], leaves)
                else:
                    dh = _block_pull(params["layers"][i], inputs.pop(), dh, **how)
    grads = jax.tree.map(lambda g: g / np.asarray(tokens).size, grads)
    return {i: _by_expert(g, kept[i]) if i in kept else g for i, g in grads.items()}
