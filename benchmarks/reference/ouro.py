"""Ouro as a ``model_type: ouro`` ``config.json`` gives it, at Ouro-2.6B's
numbers (ByteDance, https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json;
the paper "Scaling Latent Reasoning via Looped Language Models"): one stack of
decoder layers applied ``total_ut_steps`` times with the same parameters, an
exit after every pass, a loss that is the expected loss over the exits under
the distribution an exit gate defines, with an entropy term. Plain
``jax.numpy`` in float32 at ``default_matmul_precision("highest")``: no
kernels, no remat policy, no cache, no batching (one row at a time), nothing
imported from the program.

For one row of ``S`` tokens, each ``N`` an RMSNorm with its own scale:

- layer: ``x' = x + N2(Attn(N1(x)))``, ``y = x' + N4(W_down(silu(N3(x')·W_gate)
  ⊙ N3(x')·W_up))``; ``Attn`` is causal softmax attention of 16 heads of 128
  on 16 key-value heads, ``q`` and ``k`` rotated by halves (``(i, i + 64)``) by
  ``pos · theta^(-2i / 128)`` at positions ``0..S−1`` in every pass, scores
  scaled by ``128^-1/2``;
- recurrence: ``h⁰ = E[tokens]``, ``hᵗ = N_f(F(hᵗ⁻¹))``, ``t = 1..T``;
- exit ``t``: ``ℓ_t = CE(hᵗ·W_headᵀ, y)``; for ``t < T`` ``λ_t = σ(hᵗ·w_g +
  b_g)``; ``p_t = λ_t ∏_{j<t}(1 − λ_j)``, ``p_T = ∏_{j<T}(1 − λ_j)``;
- loss: ``mean_i [Σ_t p_t,i ℓ_t,i + β Σ_t p_t,i log p_t,i]``.

It reads the program's parameter tree (``layers[l]`` with ``rms_1..rms_4``,
``attn``, ``mlp``; ``rms_f``; ``exit_gate`` ``{w [d, 1], b [1]}``; ``lm_head``)
and casts a layer's leaves to float32 as it uses them, so both sides hold the
same bfloat16 weights and differ only in how they compute.

Departures from the source, each also an ``assumed`` entry of the
configuration file: the sandwich norms (``N2``, ``N4``: the paper's; the config
has no key for them); the normed state carried into the next pass; the gate's
bias; ``β = 0.1``; the rotation by halves (the config names no other). The
depth is cut (the configuration file says how). Attention scores are computed
in query blocks of ``QUERY_BLOCK`` rows each against the keys it may see, the
head's log-sum-exp in vocabulary chunks of ``VOCAB_CHUNK`` rows, each block
computed again in the backward: memory, not arithmetic.

``variant`` (``"float32"`` by default) names a deliberate fault, the controls
that the limits in the traffic file are set against (PERF.md §4): computed so,
the reference itself has to come out as not correct. ``three_passes``: ``T −
1`` passes and exits; ``uniform_exits``: ``p_t = 1 / T``, the gate left out;
``last_exit_only``: the loss is ``mean ℓ_T`` alone; ``entropy_flipped``: ``−β``;
``no_sandwich_norms``: ``N2`` and ``N4`` left out; ``unnormed_carry``: the next
pass takes ``F(hᵗ⁻¹)``, not ``N_f`` of it (the exit still reads the normed
state); ``rotary_pairs``: ``q`` and ``k`` rotated by pairs ``(2i, 2i + 1)``;
``matmuls_float8``: every matmul of the forward on operands rounded to float8
(e4m3), summed in float32, the nearest precision below the configuration's
bfloat16 (the backward's matmuls take those operands and float32
cotangents), but for the attention's probabilities: unscaled, most of a row
of 8,192 lies under e4m3's least subnormal and would flush to zero.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 1024
VOCAB_CHUNK = 8192
VARIANTS = ("float32", "three_passes", "uniform_exits", "last_exit_only", "entropy_flipped",
            "no_sandwich_norms", "unnormed_carry", "rotary_pairs", "matmuls_float8")
_IN_BLOCK = ("no_sandwich_norms", "rotary_pairs", "matmuls_float8")  # the variants a block computes otherwise


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the equations need beside the parameters, under the source's names."""

    num_attention_heads: int
    head_dim: int
    total_ut_steps: int
    rms_norm_eps: float
    rope_theta: float
    entropy_weight: float


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(jnp.float32)


@jax.custom_jvp
def _float8(t):
    """``t`` rounded to 4 exponent and 3 mantissa bits, its cotangent passed
    through in float32: unscaled, a cotangent in float8 would flush to zero
    (a softmax's ``1 / vocabulary`` lies under its least subnormal, ``2^-9``)."""
    return t.astype(jnp.float8_e4m3fn).astype(jnp.float32)


@_float8.defjvp
def _float8_jvp(primals, tangents):
    return _float8(primals[0]), tangents[0]


def _operand(t, variant):
    """A matmul's operand: ``matmuls_float8`` rounds it to float8 (:func:`_float8`)."""
    return _float8(t) if variant == "matmuls_float8" else t


def _dot(a, b, variant):
    return _operand(a, variant) @ _operand(b, variant)  # summed in float32


def _rotate(t, theta, variant):
    """``t [heads, seq, dim]`` rotated at positions ``0..seq−1``: the pair
    ``(i, i + dim / 2)`` by ``pos · theta^(-2i / dim)``; ``rotary_pairs``: the
    pair ``(2i, 2i + 1)`` by the same angle."""
    seq, dim = t.shape[-2], t.shape[-1]
    inv_freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if variant == "rotary_pairs":
        t1, t2 = t[..., 0::2], t[..., 1::2]
        return jnp.stack([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1).reshape(t.shape)
    t1, t2 = t[..., :dim // 2], t[..., dim // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1)


def _attend(q, k, v, start, variant):
    """Queries ``start ...`` of one row against the keys they may see."""
    end = start + q.shape[1]
    scores = jnp.einsum("hqd,hkd->hqk", _operand(q, variant), _operand(k, variant)) / math.sqrt(q.shape[-1])
    visible = jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,hkd->hqd", probs, _operand(v, variant))  # probabilities as they are: e4m3 flushes under 2^-9


def _attention(p, x, *, s: Sizes, variant):
    seq = x.shape[0]

    def heads(t):
        return t.reshape(seq, s.num_attention_heads, s.head_dim).transpose(1, 0, 2)

    q, k, v = (heads(_dot(x, p[name], variant)) for name in ("wq", "wk", "wv"))
    q, k = _rotate(q, s.rope_theta, variant), _rotate(k, s.rope_theta, variant)
    attend = jax.checkpoint(_attend, static_argnums=(3, 4))
    out = [attend(q[:, start:start + QUERY_BLOCK], k[:, :start + QUERY_BLOCK], v[:, :start + QUERY_BLOCK],
                  start, variant) for start in range(0, seq, QUERY_BLOCK)]
    return _dot(jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(seq, -1), p["wo"], variant)


def block(layer, h, *, s: Sizes, variant="float32"):
    """One layer on one row ``h [seq, d]``, sandwich-normed."""
    layer, eps = _f32(layer), s.rms_norm_eps
    sandwich = variant != "no_sandwich_norms"
    a = _attention(layer["attn"], _rms_norm(h, layer["rms_1"]["scale"], eps), s=s, variant=variant)
    h = h + (_rms_norm(a, layer["rms_2"]["scale"], eps) if sandwich else a)
    x, mlp = _rms_norm(h, layer["rms_3"]["scale"], eps), layer["mlp"]
    y = _dot(jax.nn.silu(_dot(x, mlp["w_gate"], variant)) * _dot(x, mlp["w_up"], variant), mlp["w_down"], variant)
    return h + (_rms_norm(y, layer["rms_4"]["scale"], eps) if sandwich else y)


def final_norm(rms_f, h, *, eps):
    return _rms_norm(h, rms_f["scale"], eps)


def head_rows(lm_head, x, targets, *, variant="float32"):
    """Each token's next-token negative log likelihood ``[seq]`` from the normed
    state ``x [seq, d]``, the untied head's log-sum-exp over vocabulary chunks."""
    def chunk_lse(x, rows):
        return jax.nn.logsumexp(_dot(x, rows.astype(jnp.float32).T, variant), axis=-1)

    chunk_lse = jax.checkpoint(chunk_lse)
    lse = jnp.full(x.shape[0], -jnp.inf)
    for start in range(0, lm_head.shape[0], VOCAB_CHUNK):
        lse = jnp.logaddexp(lse, chunk_lse(x, lm_head[start:start + VOCAB_CHUNK]))
    wanted = lm_head[targets].astype(jnp.float32)
    return lse - jnp.einsum("sd,sd->s", _operand(x, variant), _operand(wanted, variant))


def exit_objective(gate, states, losses, shift=0.0, *, s: Sizes, variant="float32"):
    """``Σ_i [Σ_t p_t,i ℓ_t,i + β Σ_t p_t,i log p_t,i]`` over one row's tokens:
    ``states [T, seq, d]`` the exits' normed states, ``losses [T, seq]`` their ℓ;
    ``shift`` (0, or ``[T − 1, seq]`` zeros) is added to the gate's logits, so
    its cotangent is each logit's, the bias's gradient term by term."""
    passes = states.shape[0]
    if variant == "last_exit_only":
        return losses[-1].sum()
    if variant == "uniform_exits":
        p = jnp.full(losses.shape, 1.0 / passes)
    else:
        gate = _f32(gate)
        lam = jax.nn.sigmoid(_dot(states[:-1], gate["w"], variant)[..., 0] + gate["b"][0] + shift)  # [T - 1, seq]
        last = jnp.prod(1.0 - lam, axis=0, keepdims=True)  # Π_{j<T} (1 - λ_j)
        before = jnp.cumprod(jnp.concatenate([jnp.ones_like(last), 1.0 - lam[:-1]]), axis=0)  # Π_{j<t} (1 - λ_j)
        p = jnp.concatenate([lam * before[:len(lam)], last])
    beta = -s.entropy_weight if variant == "entropy_flipped" else s.entropy_weight
    return (p * losses).sum() + beta * (p * jnp.log(p)).sum()


def passes(s: Sizes, variant: str) -> int:
    return s.total_ut_steps - 1 if variant == "three_passes" else s.total_ut_steps


def loss_fn(params, tokens, targets, *, s: Sizes, variant="float32", untied=False):
    """Mean loss over ``tokens`` / ``targets`` ``[rows, seq]``, one traceable
    function: what ``jax.grad`` differentiates in the tests. With ``untied``,
    ``params["layers"]`` holds one list of layers for each pass."""
    total, eps = 0.0, s.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        for row_tokens, row_targets in zip(tokens, targets):
            h, states = params["wte"][row_tokens].astype(jnp.float32), []
            for t in range(passes(s, variant)):
                for layer in params["layers"][t] if untied else params["layers"]:
                    h = block(layer, h, s=s, variant=variant)
                states.append(final_norm(params["rms_f"], h, eps=eps))
                h = h if variant == "unnormed_carry" else states[-1]
            losses = jnp.stack([head_rows(params["lm_head"], x, row_targets, variant=variant) for x in states])
            total = total + exit_objective(params["exit_gate"], jnp.stack(states), losses, s=s, variant=variant)
    return total / tokens.size


def _block_variant(variant: str) -> str:
    """The variant as a block sees it: one program for every variant that leaves blocks alone."""
    return variant if variant in _IN_BLOCK else "float32"


def _head_variant(variant: str) -> str:
    """The variant as the head sees it."""
    return variant if variant == "matmuls_float8" else "float32"


_block_jit = jax.jit(block, static_argnames=("s", "variant"))
_norm_jit = jax.jit(final_norm, static_argnames=("eps",))
_head_jit = jax.jit(head_rows, static_argnames=("variant",))


@functools.partial(jax.jit, static_argnames=("s", "variant"))
def _block_pull(layer, h, dh, *, s, variant):
    """The cotangent of one block's input alone."""
    return jax.vjp(lambda x: block(layer, x, s=s, variant=variant), h)[1](dh)[0]


@functools.partial(jax.jit, static_argnames=("s", "variant"))
def _block_pull_leaves(layer, h, dh, *, s, variant):
    """The cotangents of one block's leaves (float32) and of its input."""
    return jax.vjp(lambda p, x: block(p, x, s=s, variant=variant), _f32(layer), h)[1](dh)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm_pull(rms_f, h, dx, *, eps):
    """The cotangents of ``N_f``'s scale (float32) and of its input."""
    return jax.vjp(lambda p, x: final_norm(p, x, eps=eps), _f32(rms_f), h)[1](dx)


@functools.partial(jax.jit, static_argnames=("variant",))
def _head_pull(lm_head, x, targets, d_rows, *, variant):
    return jax.vjp(lambda x: head_rows(lm_head, x, targets, variant=variant), x)[1](d_rows)[0]


@functools.partial(jax.jit, static_argnames=("variant",))
def _head_pull_table(lm_head, x, targets, d_rows, *, variant):
    """The cotangents of the head's table (float32) and of its input."""
    return jax.vjp(lambda w, x: head_rows(w, x, targets, variant=variant), _f32(lm_head), x)[1](d_rows)


@functools.partial(jax.jit, static_argnames=("s", "variant"))
def _objective_grads(gate, states, losses, *, s, variant):
    """``(value, (d gate, d states, d losses, d logits))`` of one row's exit
    objective, ``d logits [T − 1, seq]`` the cotangent of each gate logit."""
    shift = jnp.zeros((states.shape[0] - 1, states.shape[1]))
    return jax.value_and_grad(functools.partial(exit_objective, s=s, variant=variant), argnums=(0, 1, 2, 3))(
        _f32(gate), states, losses, shift)


def _forward(params, row_tokens, s: Sizes, variant: str):
    """One row's walk: ``(inputs, pre, states)``, the input of every block
    application by pass, each pass's output before ``N_f`` and after it."""
    how = dict(s=s, variant=_block_variant(variant))
    h, inputs, pre, states = params["wte"][row_tokens].astype(jnp.float32), [], [], []
    for _ in range(passes(s, variant)):
        inputs.append([])
        for layer in params["layers"]:
            inputs[-1].append(h)
            h = _block_jit(layer, h, **how)
        pre.append(h)
        states.append(_norm_jit(params["rms_f"], h, eps=s.rms_norm_eps))
        h = h if variant == "unnormed_carry" else states[-1]
    return inputs, pre, states


def loss(params, tokens, targets, *, s: Sizes, variant="float32") -> float:
    """The same number for host int arrays at the published widths: each block
    one jitted program called per layer, pass and row, one layer's weights in
    float32 at a time."""
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for row_tokens, row_targets in zip(np.asarray(tokens), np.asarray(targets)):
            states = _forward(params, row_tokens, s, variant)[2]
            losses = jnp.stack([_head_jit(params["lm_head"], x, row_targets, variant=_head_variant(variant))
                                for x in states])
            total += float(_objective_grads(params["exit_gate"], jnp.stack(states), losses, s=s, variant=variant)[0])
    return total / np.asarray(tokens).size


def grads(params, tokens, targets, layers, *, s: Sizes, variant="float32", tables=False) -> dict:
    """``{"layers": {i: the float32 gradient of the mean loss by the leaves of
    params["layers"][i]}, "exit_gate": ..., "rms_f": ..., "exit_gate_terms":
    Σ_t,i |∂loss / ∂z_t,i|}`` for ``i`` in ``layers``, at the published widths
    beside the trained state; ``z_t,i`` is the gate's logit of token ``i`` at
    exit ``t``, so the last is the sum of the magnitudes of the terms whose sum
    is the bias's gradient. With ``tables``, also ``"wte"`` and ``"lm_head"``.
    The forward keeps each block application's input; the backward takes each
    exit's cotangent from the objective and the head, then pulls the residual
    stream's cotangent down one block application at a time, pass by pass, and
    sums each watched leaf's over its passes."""
    how = dict(s=s, variant=_block_variant(variant))
    out: dict = {"layers": {}, "exit_gate": None, "rms_f": None, "exit_gate_terms": None}
    if tables:
        out.update(wte=None, lm_head=None)

    def add(into, key, tree):
        into[key] = tree if into[key] is None else jax.tree.map(jnp.add, into[key], tree)

    with jax.default_matmul_precision("highest"):
        for row_tokens, row_targets in zip(np.asarray(tokens), np.asarray(targets)):
            inputs, pre, states = _forward(params, row_tokens, s, variant)
            losses = jnp.stack([_head_jit(params["lm_head"], x, row_targets, variant=_head_variant(variant))
                                for x in states])
            _, (d_gate, d_states, d_losses, d_logits) = _objective_grads(params["exit_gate"], jnp.stack(states),
                                                                         losses, s=s, variant=variant)
            add(out, "exit_gate", d_gate)
            add(out, "exit_gate_terms", jnp.abs(d_logits).sum())
            dh = None  # the cotangent of what the pass after this one took in
            for t in reversed(range(len(states))):
                if tables:
                    d_table, d_state = _head_pull_table(params["lm_head"], states[t], row_targets, d_losses[t],
                                                        variant=_head_variant(variant))
                    add(out, "lm_head", d_table)
                else:
                    d_state = _head_pull(params["lm_head"], states[t], row_targets, d_losses[t],
                                         variant=_head_variant(variant))
                d_state = d_state + d_states[t]
                if dh is not None and variant != "unnormed_carry":
                    d_state = d_state + dh
                d_scale, dh_pass = _norm_pull(params["rms_f"], pre[t], d_state, eps=s.rms_norm_eps)
                add(out, "rms_f", d_scale)
                dh = dh_pass if dh is None or variant != "unnormed_carry" else dh_pass + dh
                for i in reversed(range(len(params["layers"]))):
                    h, inputs[t][i] = inputs[t][i], None  # each input is let go once pulled through
                    if i in layers:
                        leaves, dh = _block_pull_leaves(params["layers"][i], h, dh, **how)
                        out["layers"][i] = leaves if i not in out["layers"] else jax.tree.map(
                            jnp.add, out["layers"][i], leaves)
                    else:
                        dh = _block_pull(params["layers"][i], h, dh, **how)
            if tables:  # dh is now the cotangent of the embedded row
                add(out, "wte", jnp.zeros(params["wte"].shape, jnp.float32).at[row_tokens].add(dh))
    return jax.tree.map(lambda g: g / np.asarray(tokens).size, out)
