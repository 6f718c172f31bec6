"""DeepSeek-V3 as a ``model_type: deepseek_v3`` ``config.json`` gives it, at
Kanana-2-30B-A3B's numbers (kakaocorp,
https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json):
token embedding, pre-RMSNorm blocks ``h += MLA(RMSNorm(h))``, ``h +=
FFN(RMSNorm(h))``, a final RMSNorm, an untied head, mean next-token cross
entropy; no bias anywhere. Plain ``jax.numpy`` in float32 at
``default_matmul_precision("highest")``: no kernels, no sort, no grouped product,
no cache, no batching (one row at a time), nothing imported from the program.

Attention (MLA, no query latent): ``q = x·Wq`` as ``[heads, seq, 128 + 64]``;
``[c, k_pe] = x·Wkv_a`` (``c`` ``kv_lora_rank`` wide, ``k_pe`` one 64-wide head);
``c = RMSNorm(c)``; ``[k_nope, v] = c·Wkv_b`` as ``[heads, seq, 128 + 128]``;
``q_pe`` and ``k_pe`` rotated by pairs ``(2i, 2i+1)`` through ``pos ·
theta^(-2i / 64)`` (``rope_interleave``, the closed form :func:`_rotate`); ``k =
[k_nope, k_pe]`` with ``k_pe`` the same for every head; scores ``q·kᵀ /
sqrt(192)`` under a dense causal mask, softmax in float32, ``·v``, ``·Wo``.

Feed-forward: layers below ``first_k_dense_replace`` a gated SiLU MLP of
``intermediate_size``. Every other: scores ``s = sigmoid(x·Wr)`` in float32, each
token's ``num_experts_per_tok`` experts the largest ``s + b`` (``b`` the
selection bias, ``e_score_correction_bias``: ``noaux_tc``), weights ``w =
routed_scaling_factor · s_top / Σ s_top`` (without ``b``), ``y = Σ_e w_e ·
(SiLU(x·Wg_e) ⊙ (x·Wu_e))·Wd_e + shared(x)`` with ``shared`` one gated SiLU MLP of
``n_shared_experts · moe_intermediate_size``. EVERY expert held is evaluated for
EVERY token and weighted by a ``[tokens, experts]`` matrix that is zero off the
chosen ones. ``experts_held = (first, count)`` is the chip's share of the
deployment: the router and the choice are over all experts, the sum over the
held ones (whose matrices are the leaves' leading axis); the shared experts
are the chip's own for its own tokens, counted once.

It reads the program's parameter tree and casts a layer's leaves to float32 as
it uses them (an expert at a time), so both sides hold the same bfloat16 weights
and differ only in how they compute.

Departures from the source, each also an ``assumed`` entry of the configuration
file: the group limit (``n_group`` = ``topk_group`` = 1) selects every expert
and is not built; the selection bias is a drawn constant (no update between
steps); no sequence-wise balance loss (the config has no coefficient for one).
The depth, the experts held and the vocabulary are cut (the configuration file
lists each). Scores are computed in query blocks of ``QUERY_BLOCK`` rows (each
against every key), the experts over ``TOKEN_CHUNK`` tokens at a time with each
expert's step recomputed in the backward, the head's log-sum-exp in vocabulary
chunks of ``VOCAB_CHUNK`` rows: memory, not arithmetic.

``variant`` (``"float32"`` by default) names a deliberate fault, the controls
that the limits in the traffic file are set against (PERF.md §4): computed so,
the reference itself has to come out as not correct. ``half_split_rotary``:
the rotary parts rotated by halves (``(i, i + 32)``) in place of pairs;
``softmax_router``: ``softmax(x·Wr)`` in place of the sigmoid;
``bias_in_weights``: ``w`` from ``s + b``; ``no_routed_scaling``;
``no_latent_norm``; ``no_shared_experts``; ``scale_128``: the scores divided by
``sqrt(128)``; ``experts_float8``: the experts' matmuls on operands rounded to
float8 (e4m3), the nearest precision below the configuration's bfloat16;
``sigmoid_derivative``: the router's sigmoid differentiated as ``tanh`` is,
``1 - s²`` in place of ``s·(1 - s)``, its forward unchanged (a fault the
loss cannot see and the router's gradient carries first).
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
VARIANTS = ("float32", "half_split_rotary", "softmax_router", "bias_in_weights", "no_routed_scaling",
            "no_latent_norm", "no_shared_experts", "scale_128", "experts_float8", "sigmoid_derivative")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the equations need beside the parameters, under the source's names."""

    num_attention_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    layer_types: tuple
    rms_norm_eps: float
    rope_theta: float
    experts_held: tuple | None = None


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate(t, s: Sizes, variant: str):
    """``t [..., seq, qk_rope_head_dim]`` rotated: pair ``(2i, 2i+1)`` by the
    angle ``pos · theta^(-2i / qk_rope_head_dim)``; ``half_split_rotary``:
    the pair ``(i, i + half)`` by the same angle."""
    seq, dim = t.shape[-2], t.shape[-1]
    inv_freq = s.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if variant == "half_split_rotary":
        t1, t2 = t[..., :dim // 2], t[..., dim // 2:]
        return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1)
    t1, t2 = t[..., 0::2], t[..., 1::2]
    return jnp.stack([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1).reshape(t.shape)


def _attend(q, k, v, start, scale):
    """Queries ``start ...`` of one row against every key, causal: q ``[heads,
    block, qk]``, k ``[heads, seq, qk]``, v ``[heads, seq, v_head_dim]``."""
    scores = jnp.einsum("hqd,hkd->hqk", q, k) * scale
    visible = (start + jnp.arange(q.shape[1]))[:, None] >= jnp.arange(k.shape[1])[None, :]
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1), v)


def _attention(p, x, *, s: Sizes, variant):
    seq, heads, nope = x.shape[0], s.num_attention_heads, s.qk_nope_head_dim
    q = (x @ p["wq"]).reshape(seq, heads, -1).transpose(1, 0, 2)
    latent = x @ p["wkv_a"]
    c, k_pe = latent[:, :s.kv_lora_rank], latent[:, s.kv_lora_rank:]
    if variant != "no_latent_norm":
        c = _rms_norm(c, p["kv_norm"]["scale"], s.rms_norm_eps)
    kv = (c @ p["wkv_b"]).reshape(seq, heads, -1).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], s, variant)], axis=-1)
    k_pe = _rotate(k_pe[None], s, variant)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (heads, seq, s.qk_rope_head_dim))], axis=-1)
    v = kv[..., nope:]
    scale = 1.0 / math.sqrt(nope if variant == "scale_128" else nope + s.qk_rope_head_dim)
    # query blocks one after another, each block's scores computed again in the backward
    block = math.gcd(seq, QUERY_BLOCK)
    blocks = q.reshape(heads, seq // block, block, -1).transpose(1, 0, 2, 3)
    attend = jax.checkpoint(lambda _, part: (None, _attend(part[0], k, v, part[1], scale)))
    out = jax.lax.scan(attend, None, (blocks, jnp.arange(0, seq, block)))[1]  # [blocks, heads, block, v]
    out = out.transpose(1, 0, 2, 3).reshape(heads, seq, s.v_head_dim)
    return out.transpose(1, 0, 2).reshape(seq, -1) @ p["wo"]


@jax.custom_jvp
def _sigmoid_as_tanh(z):
    """``sigmoid(z)``, differentiated as ``tanh`` is: the ``sigmoid_derivative`` fault."""
    return jax.nn.sigmoid(z)


@_sigmoid_as_tanh.defjvp
def _sigmoid_as_tanh_jvp(primals, tangents):
    s = jax.nn.sigmoid(primals[0])
    return s, (1.0 - s * s) * tangents[0]


def routing_weights(x, w_router, bias, *, s: Sizes, variant="float32"):
    """``[tokens, experts]`` float32: each token's weight on its chosen
    experts, zero elsewhere."""
    logits = x @ w_router
    if variant == "softmax_router":
        score = jax.nn.softmax(logits, axis=-1)
    else:
        score = (_sigmoid_as_tanh if variant == "sigmoid_derivative" else jax.nn.sigmoid)(logits)
    _, top_e = jax.lax.top_k(score + bias, s.num_experts_per_tok)
    chosen = jax.nn.one_hot(top_e, score.shape[-1], dtype=jnp.float32).sum(1)  # [tokens, experts] 0/1
    top = chosen * (score + bias if variant == "bias_in_weights" else score)
    weight = top / top.sum(-1, keepdims=True)
    return weight if variant == "no_routed_scaling" else weight * s.routed_scaling_factor


def _dot(a, b, variant):
    if variant == "experts_float8":  # operands of 4 exponent and 3 mantissa bits, summed in float32
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32) @ b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return a @ b


def _experts(moe, x, weight, variant):
    """``Σ_e weight[:, e] · expert_e(x)`` over the experts held, one expert
    and ``TOKEN_CHUNK`` tokens at a time; ``weight [tokens, held]``. Both loops
    are ``lax.scan``s whose steps are computed again in the backward."""
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


def _mlp(p, x):
    return (jax.nn.silu(x @ p["w_gate"].astype(jnp.float32)) * (x @ p["w_up"].astype(jnp.float32))) @ p["w_down"].astype(jnp.float32)


def moe(p, x, *, s: Sizes, variant="float32"):
    """The routed experts on one row ``x [seq, d]``: the held experts' part."""
    weight = routing_weights(x, p["router"].astype(jnp.float32), p["bias"].astype(jnp.float32), s=s, variant=variant)
    first, count = s.experts_held or (0, weight.shape[1])
    return _experts(p, x, weight[:, first:first + count], variant)


def block(layer, h, *, kind, s: Sizes, variant="float32"):
    """One block on one row ``h [seq, d]``; ``kind`` ``dense`` or ``sparse``."""
    x = _rms_norm(h, layer["rms_1"]["scale"].astype(jnp.float32), s.rms_norm_eps)
    h = h + _attention(_f32(layer["attn"]), x, s=s, variant=variant)
    x = _rms_norm(h, layer["rms_2"]["scale"].astype(jnp.float32), s.rms_norm_eps)
    if kind == "dense":
        return h + _mlp(layer["mlp"], x)
    y = moe(layer["moe"], x, s=s, variant=variant)
    return h + (y if variant == "no_shared_experts" else y + _mlp(layer["shared"], x))


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
_EXPERT_MATRICES = ("w_gate", "w_up", "w_down")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _block_pull(layer, h, dh, **how):
    """The cotangent of one block's input alone."""
    return jax.vjp(lambda x: block(layer, x, **how), h)[1](dh)[0]


def _by_expert(layer, experts, cut=lambda leaf, at, e: leaf[at]):
    """``layer`` with each of ``experts`` a subtree of its own under
    ``moe.experts``, its three matrices read out of the stacked leaves."""
    rest = {name: leaf for name, leaf in layer["moe"].items() if name not in _EXPERT_MATRICES}
    return {**layer, "moe": {**rest, "experts": {e: {name: cut(layer["moe"][name], at, e) for name in _EXPERT_MATRICES}
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
    the experts kept: all (``experts`` None and ``busiest`` 0, or a dense
    block), the ``experts`` named, or the ``busiest`` experts, those whose
    ``w_down`` has the largest gradient. The expert matrices go in as they are
    stored (an expert is cast up as it is used), so their cotangent is rounded
    to that type where it leaves the sum over a chunk of tokens; every other
    leaf goes in as float32."""
    if "moe" not in layer:
        leaves, dh = jax.vjp(lambda p, x: block(p, x, **how), _f32(layer), h)[1](dh)
        return leaves, None, dh
    moe = {name: leaf if name in _EXPERT_MATRICES else leaf.astype(jnp.float32) for name, leaf in layer["moe"].items()}
    layer = {**_f32({name: leaves for name, leaves in layer.items() if name != "moe"}), "moe": moe}
    leaves, dh = jax.vjp(lambda p, x: block(p, x, **how), layer, h)[1](dh)
    if experts is None and not busiest:
        return _f32(leaves), None, dh
    if experts is None:
        norms = jnp.sum(jnp.square(leaves["moe"]["w_down"].astype(jnp.float32)), axis=(1, 2))
        pick = jnp.sort(jax.lax.top_k(norms, busiest)[1])
    else:
        pick = jnp.asarray(experts)
    moe = {name: leaf[pick] if name in _EXPERT_MATRICES else leaf for name, leaf in leaves["moe"].items()}
    return _f32({**leaves, "moe": moe}), pick, dh


def loss(params, tokens, targets, *, s: Sizes, variant="float32") -> float:
    """The same number for host int arrays at the published widths: each type
    of block is one jitted program called per layer and per row."""
    return float(_mean_loss(_block_jit, _head_jit, params, np.asarray(tokens), np.asarray(targets),
                            s=s, variant=variant))


def layer_grads(params, tokens, targets, layers, *, s: Sizes, variant="float32", experts=None, busiest=0) -> dict:
    """``{i: the float32 gradient of the mean loss by the leaves of
    params["layers"][i]}`` for ``i`` in ``layers``, at the published widths
    beside the trained state: the forward keeps each block's input, the
    backward pulls the cotangent down one block at a time and stops at the
    lowest layer asked for. Of an expert layer's expert leaves: all; or, in
    the form of :func:`watched_leaves`, the ``experts`` named (``{layer:
    indices}``) or each layer's ``busiest`` experts by the first row's gradient."""
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
