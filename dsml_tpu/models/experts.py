"""A layer of sparse gated experts as it is deployed: every token goes to its
``top_k`` experts, none is dropped, and the work is a function of shapes alone.

``y_t = Σ_e (w_te · SiLU(x_t·Wg_e) ⊙ (x_t·Wu_e))·Wd_e`` over the ``top_k`` experts
``e`` with the largest router probabilities ``softmax(x_t·Wr)``, their weights
renormalised to sum to one; or, with a selection bias beside the router, over
the ``top_k`` largest ``sigmoid(x_t·Wr) + bias``, their sigmoid scores
renormalised and scaled (:func:`route`). Four steps, each under its own scope:

- ``moe_route``: the router in float32, ``top_k``, and the plan: the ``T·top_k``
  (token, expert) pairs stable-sorted by expert into one row buffer, each
  expert's rows padded to whole tiles (:func:`plan`). The plan is integers only
  and is tagged (:data:`PLAN_NAMES`, as ``row_w`` is) so that a block recomputed
  in the backward can keep it and not sort twice.
- ``moe_dispatch``: the rows gathered into the buffer ``[rows, d]``, and each
  row's weight ``row_w [rows]`` beside them (0 on a padding row).
- ``experts``: three grouped matmuls (``ops/grouped_matmul.py``: ``gmm_fwd``,
  and ``gmm_dx`` / ``gmm_dw`` in the backward) with the gate between them. The
  weight lies on the gate, ``mid = SiLU(gate) ⊙ up · row_w`` in float32 and one
  cast, BEFORE ``Wd``, so the backward needs nothing of the down projection's
  output: a block recomputed there runs two grouped matmuls, not three, and
  ``dw`` is a row sum inside the gate's backward.
- ``moe_combine``: each token's ``top_k`` rows gathered back and summed.

**Two movements, each the other's backward** (:func:`_moved`): token -> rows
(``x[row_pair // top_k]``) and rows -> token (``Σ_j rows[dest[t, j]]`` in float32
over the pairs that have a row). The dispatch is the first with the second as
its backward, the combine the second with the first; the weights go the first
way too. Both are gathers (a row's cotangent is read from where its pair went).

**Padding rows** hold token 0's ``x`` and, in the backward, token 0's ``dy``:
the spread is not masked. ``ops/grouped_matmul.py`` wants them to reach
``gmm_dw`` as zeros, and the zero comes from the weight: ``mid · row_w`` is 0
there, so ``dWd`` sees nothing, and ``d_mid = d(mid · w) · row_w`` is 0 there, so
``dWg``, ``dWu`` and ``dx`` see nothing; no token reads a padding row back.

The buffer holds ``T·min(top_k, held) / tile + held`` tiles whatever the router
does (``n_row_tiles``) and every one is computed: at uniform routing and with
every token on one set of ``top_k`` experts the layer does the same work (a
cell whose work followed the router could not be measured: PERF.md §6, PR 35).

``experts_held = (first, count)`` is the chip's share of a deployment that
spreads a layer's experts over several chips: the router stays as wide as
published and picks over all experts; the pairs of experts that are not held
get no row, and the layer returns the held experts' part of ``y``. The shares
of all chips add up to the whole layer (``tests/test_mellum.py``). No code
stands in for the other chips or their exchange.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from dsml_tpu.ops.grouped_matmul import grouped_matmul, n_row_tiles

__all__ = ["PLAN_NAMES", "expert_layer", "plan", "route"]

# the integers of a layer's routing and each row's weight: under `jax.checkpoint` with
# `save_only_these_names(*PLAN_NAMES)` the recomputed forward reads them back (1.3 MB a layer at 8,192
# tokens) where it would run top-k, the sort, the index arithmetic and a gather of scalars a second time
PLAN_NAMES = ("moe_top_e", "moe_row_pair", "moe_dest", "moe_tile_group", "moe_row_w")


def route(x, w_router, top_k: int, bias=None, scale: float = 1.0):
    """``(top_e [T, k] int32, w [T, k] float32)``: each token's ``top_k``
    experts and their weights, the logits in float32, ties to the lower index.

    Softmax (``bias`` None): the ``top_k`` largest probabilities
    ``softmax(x·Wr)``, renormalised to sum to one. Sigmoid (``bias [E]``, a
    per-expert selection bias): scores ``s = sigmoid(x·Wr)``, the ``top_k``
    largest ``s + bias`` chosen, and the chosen ``s`` (without the bias)
    renormalised and multiplied by ``scale`` (``routed_scaling_factor``). The
    bias takes part in the choice alone, so no gradient reaches it."""
    logits = jnp.dot(x, w_router, preferred_element_type=jnp.float32)
    if bias is None:
        p = pick = jax.nn.softmax(logits, axis=-1)
    else:
        p = jax.nn.sigmoid(logits)
        pick = p + bias.astype(jnp.float32)
    top_e = checkpoint_name(lax.top_k(lax.stop_gradient(pick), top_k)[1].astype(jnp.int32), "moe_top_e")
    # the chosen probabilities by a 0/1 product, not a gather: its transpose is a product too
    chosen = top_e[:, :, None] == jnp.arange(p.shape[-1], dtype=jnp.int32)
    top_p = jnp.sum(jnp.where(chosen, p[:, None, :], 0.0), axis=-1)
    w = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_e, w if bias is None else w * scale


def plan(top_e, held: tuple[int, int], tile: int):
    """Where each (token, expert) pair goes, from ``top_e [T, k]`` alone:
    ``(row_pair [rows], dest [T, k], tile_group [rows / tile])``, all int32.

    The pairs of the ``count`` experts from ``first`` on, stable-sorted by
    expert, lie in one buffer, each expert's run starting on a tile boundary
    and owning a tile at least; the tiles past the last run belong to the last
    expert and hold no pair. ``row_pair[r]`` is the pair ``t·k + j`` in row
    ``r`` (-1: padding), ``dest[t, j]`` the row of pair ``(t, j)`` (-1: its
    expert is not held), ``tile_group[i]`` the expert (counted from ``first``)
    whose matrix tile ``i`` multiplies."""
    first, count = held
    (n_tokens, k), pairs = top_e.shape, top_e.size
    n_tiles = n_row_tiles(n_tokens * min(k, count), count, tile)
    expert = top_e.reshape(pairs) - first
    is_held = (expert >= 0) & (expert < count)
    key = jnp.where(is_held, expert, count)  # absent experts sort last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)  # the pair at each sorted position
    member = key[:, None] == jnp.arange(count, dtype=jnp.int32)  # [pairs, count]: the pair's expert, one-hot
    sizes = jnp.sum(member, axis=0, dtype=jnp.int32)
    start = jnp.cumsum(sizes) - sizes  # of each group among the sorted pairs
    tiles = jnp.maximum(1, -(-sizes // tile))
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile  # of each group in the buffer
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles, dtype=jnp.int32), side="right"), count - 1
    ).astype(jnp.int32)
    group = jnp.repeat(tile_group, tile)
    offset = jnp.arange(n_tiles * tile, dtype=jnp.int32) - row_start[group]
    row_pair = jnp.where(offset < sizes[group], order[jnp.clip(start[group] + offset, 0, pairs - 1)], -1)
    # a pair's place in its group is its rank among the pairs of that expert: the sort is stable
    rank = jnp.sum(jnp.where(member, jnp.cumsum(member, axis=0, dtype=jnp.int32), 0), axis=1) - 1
    dest = jnp.where(is_held, row_start[jnp.minimum(key, count - 1)] + rank, -1).reshape(n_tokens, k)
    return (checkpoint_name(row_pair, "moe_row_pair"), checkpoint_name(dest, "moe_dest"),
            checkpoint_name(tile_group, "moe_tile_group"))


def _to_rows(x, row_pair, dest):
    """Token -> rows: ``x [T, n]`` -> ``[rows, n]``, row ``r`` the entry of its
    pair's token; a padding row holds token 0's."""
    return x[jnp.maximum(row_pair, 0) // dest.shape[1]]


def _to_tokens(rows, row_pair, dest):
    """Rows -> token: ``[rows, n]`` -> ``[T, n]``, ``Σ_j rows[dest[t, j]]`` in
    float32 over the pairs that have a row."""
    picked = rows[jnp.maximum(dest, 0)].astype(jnp.float32)
    return jnp.sum(jnp.where((dest >= 0)[:, :, None], picked, 0.0), axis=1).astype(rows.dtype)


def _moved(move, back, scope: str):
    """``move(a, row_pair, dest)`` with ``back``, its transpose, as its
    backward under ``scope``: each of the two movements is the other's."""
    moved = jax.custom_vjp(move)

    def bwd(row_plan, d):
        with jax.named_scope(scope):
            return back(d, *row_plan), None, None

    moved.defvjp(lambda a, *row_plan: (move(a, *row_plan), row_plan), bwd)
    return moved


_dispatch = _moved(_to_rows, _to_tokens, "moe_dispatch")
_collect = _moved(_to_tokens, _to_rows, "moe_combine")


def expert_layer(p: dict, x, *, top_k: int, tile: int, experts_held: tuple[int, int] | None = None,
                 routed_scaling: float = 1.0):
    """``x [T, d]`` -> the held experts' part of the layer's output ``[T, d]``.
    ``p``: ``router [d, E]``, and of the experts held ``w_gate``, ``w_up``
    ``[held, d, f]``, ``w_down [held, f, d]``; with ``bias [E]`` beside the
    router the sigmoid router with that selection bias and ``routed_scaling``
    (:func:`route`), else the softmax. ``tile`` is the row tile of the
    grouped matmuls; ``experts_held = (first, count)``, by default all."""
    held = experts_held or (0, p["router"].shape[1])
    if p["w_gate"].shape[0] != held[1]:
        raise ValueError(f"{p['w_gate'].shape[0]} experts' weights for experts_held={held}")
    with jax.named_scope("moe_route"):
        top_e, w = route(x, p["router"], top_k, p.get("bias"), routed_scaling)
        row_pair, dest, tile_group = plan(top_e, held, tile)
    with jax.named_scope("moe_dispatch"):
        rows = _dispatch(x, row_pair, dest)
        # the weights go the rows' way (a gather of scalars by pair is slower than this one of a token's
        # top_k), and a row keeps its own pair's: [T, k] -> [rows, k] -> [rows], 0 on a padding row
        own = (row_pair[:, None] >= 0) & (row_pair[:, None] % top_k == jnp.arange(top_k))
        row_w = checkpoint_name(jnp.sum(jnp.where(own, _dispatch(w, row_pair, dest), 0.0), axis=1), "moe_row_w")
    with jax.named_scope("experts"):
        gate, up = (grouped_matmul(rows, p[name], tile_group, tile).astype(jnp.float32) for name in ("w_gate", "w_up"))
        mid = (jax.nn.silu(gate) * up * row_w[:, None]).astype(rows.dtype)
        rows = grouped_matmul(mid, p["w_down"], tile_group, tile)
    with jax.named_scope("moe_combine"):
        return _collect(rows, row_pair, dest)
