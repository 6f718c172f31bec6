"""Speculative decoding with prompt-lookup (n-gram) drafting — TPU-first.

Greedy KV-cache decode emits one token per model call; each call is
memory-bound (the whole model streams from HBM per token). Speculative
decoding scores a WINDOW of C candidate tokens in one call
(``model.verify_step`` — multi-query decode, the same machinery as
chunked prefill) and accepts the longest prefix that matches the model's
own greedy choices, so one HBM sweep can yield up to C tokens. The draft
comes from prompt lookup (n-gram matching against the already-seen
tokens — Saxena's "prompt lookup decoding", the vLLM ngram speculator):
no draft model, free proposals, large wins exactly where decode is
longest (summarization/code/chat with reuse of earlier spans).

The ENTIRE decode loop — n-gram lookup, draft gather, verify, accept,
cache/history update — runs inside ONE jitted ``lax.while_loop``: static
shapes throughout, zero host round trips per token (a host-looped
speculator would pay one dispatch per step and lose what it won).
Guaranteed progress ≥ 1 token per iteration, so the loop is bounded
by ``max_new_tokens`` iterations.

Token-level guarantee: greedy speculative output is IDENTICAL to plain
greedy ``generate`` (tests pin it). Acceptance only changes how many
model calls it takes, never what tokens come out:

- verify feeds [last_accepted, d_1..d_{C-1}] at positions p..p+C-1;
- g_i = argmax(logits[i]) is the greedy continuation after consuming
  token i of that window; d_{i+1} is accepted iff it equals g_i and all
  earlier drafts were accepted; the first non-matching position emits
  g_acc (the model's own token), exactly what step-by-step greedy decode
  would have produced.

Rejected drafts leave garbage K/V rows beyond the accepted prefix; the
next verify window starts at the first garbage row and is at least as
long, so garbage is always overwritten before any query can attend to it
(``verify_step`` docstring carries the full argument).

Reference: the upstream has no inference path at all (SURVEY.md §5);
this module is beyond-reference serving capability on top of the
framework's decode stack, model-generic (GPT-2 and Llama share
``verify_step`` through ``_decode_core``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["generate_speculative", "lookup_draft_host", "lookup_draft_batch"]


def lookup_draft_host(history: np.ndarray, n: int, k: int) -> np.ndarray:
    """Prompt-lookup draft, HOST side (numpy): the ``k`` tokens that
    followed the MOST RECENT prior occurrence of ``history``'s trailing
    n-gram; repeats the last token when no match exists (acceptance then
    falls to the guaranteed +1-token/tick floor — wrong drafts only cost
    speed, never tokens). THE one host drafting rule: the continuous
    batcher's speculative tick drafts through here, and
    :func:`lookup_draft_batch` is the same rule device-side (the
    in-``lax.while_loop`` speculator) — equivalence pinned in tests."""
    history = np.asarray(history)
    length = len(history)
    n = min(n, length)
    gram = history[length - n:]
    win = np.lib.stride_tricks.sliding_window_view(history, n)  # [L-n+1, n]
    # exclude only the trailing gram itself (windows ending before the last
    # position; overlap with the gram region is allowed) — the same rule as
    # the device-side lookup (j + n - 1 < pos)
    matches = np.flatnonzero(np.all(win[: length - n] == gram, axis=1))
    if len(matches) == 0:
        return np.full(k, history[-1], np.int32)
    best = int(matches[-1])
    src = history[best + n : best + n + k].astype(np.int32)
    if len(src) < k:  # match near the end: pad with last-token repeats
        src = np.concatenate([src, np.full(k - len(src), history[-1], np.int32)])
    return src


def lookup_draft_batch(hbuf: jax.Array, pos: jax.Array, window: int,
                      ngram: int) -> jax.Array:
    """Prompt-lookup draft, DEVICE side (traceable): for each row of
    ``hbuf`` [b, max_seq] whose last accepted token sits at ``pos[b]``,
    the ``window - 1`` tokens that followed the most recent match of the
    trailing ``ngram``-gram strictly inside accepted history
    (``j + ngram - 1 < pos``); no match → repeat the last token. Static
    ``ngram`` unrolls into shifted equalities — no gather, no sort.
    Shared by the jitted speculative ``while_loop`` and (via vmap in
    tests) pinned equivalent to :func:`lookup_draft_host`."""
    b, max_seq = hbuf.shape
    n, c = ngram, window
    jidx = jnp.arange(max_seq - n + 1, dtype=jnp.int32)
    # gram[b] = hbuf[b, pos-n+1 .. pos]
    gram = jax.vmap(
        lambda h, p: lax.dynamic_slice_in_dim(h, p - (n - 1), n)
    )(hbuf, pos)  # [b, n]
    match = jnp.ones((b, max_seq - n + 1), bool)
    for i in range(n):  # static n (2-3): unrolled shifted equality
        match &= hbuf[:, i : max_seq - n + 1 + i] == gram[:, i : i + 1]
    # window must end strictly inside accepted history (j+n-1 < pos)
    legal = jidx[None, :] <= pos[:, None] - n
    best = jnp.max(jnp.where(match & legal, jidx[None, :], -1), axis=1)  # [b]
    found = best >= 0
    src = best[:, None] + n + jnp.arange(c - 1, dtype=jnp.int32)[None, :]
    # a match near the end runs out of followers: read the LAST ACCEPTED
    # token instead of whatever sits past pos in the buffer (unfilled or
    # stale rows) — the host rule's pad-with-last, and a strictly better
    # draft than garbage (wrong drafts only cost speed, never tokens)
    src = jnp.where(src <= pos[:, None], src, pos[:, None])
    draft = jnp.take_along_axis(hbuf, src, axis=1)
    return jnp.where(found[:, None], draft, gram[:, -1:])  # [b, C-1]


def _build_speculative_fn(model, prompt_len: int, max_new: int, window: int, ngram: int):
    """The jitted speculative decode program for static shapes
    (prompt_len, max_new, window=C, ngram=n). Returns
    ``run(params, prompt) -> (tokens [b, max_new], n_calls [])``."""
    cfg = model.config
    max_seq = cfg.max_seq
    c = window  # tokens scored per verify call (1 real + C-1 drafts)
    n = ngram

    def run(params, prompt):
        b, t = prompt.shape
        # history buffer: prompt now, emitted tokens appended as they are
        # ACCEPTED — positions <= pos[b] always hold real tokens, and the
        # final output is simply hbuf[:, t : t + max_new]
        hbuf = jnp.zeros((b, max_seq), jnp.int32).at[:, :t].set(prompt)

        # prefill the prompt (logits at t-1 give the first greedy token)
        logits0, cache = model.prefill(params, prompt, last_index=t - 1)
        first = jnp.argmax(logits0, axis=-1).astype(jnp.int32)  # [b]
        hbuf = hbuf.at[:, t].set(first)
        pos = jnp.full((b,), t, jnp.int32)  # position of last accepted token
        n_gen = jnp.ones((b,), jnp.int32)

        def body(state):
            hbuf, cache, pos, n_gen, calls = state
            draft = lookup_draft_batch(hbuf, pos, c, n)
            last = jnp.take_along_axis(hbuf, pos[:, None], axis=1)  # [b, 1]
            window_toks = jnp.concatenate([last, draft], axis=1)  # [b, C]
            logits, cache = model.verify_step(params, cache, window_toks, pos)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [b, C]
            # accepted = longest prefix of drafts matching the greedy chain
            matches = draft == g[:, : c - 1]
            acc = jnp.sum(jnp.cumprod(matches.astype(jnp.int32), axis=1), axis=1)  # [b]
            # emit vector: accepted drafts then the model's own next token
            i_idx = jnp.arange(c, dtype=jnp.int32)[None, :]
            vshift = jnp.concatenate(
                [draft, jnp.zeros((b, 1), jnp.int32)], axis=1
            )  # v[:, i+1] for i in 0..C-1 (junk at i = C-1 when acc = C-1)
            g_at_acc = jnp.take_along_axis(g, acc[:, None], axis=1)  # [b, 1]
            emit = jnp.where(
                i_idx < acc[:, None], vshift,
                jnp.where(i_idx == acc[:, None], g_at_acc, 0),
            )  # [b, C]
            # rows that already hit max_new freeze (their writes land beyond
            # the output region and their pos stops advancing)
            adv = jnp.minimum(acc + 1, jnp.maximum(max_new - n_gen, 0))
            hbuf = jax.vmap(
                lambda h, e, p: lax.dynamic_update_slice_in_dim(h, e, p + 1, axis=0)
            )(hbuf, emit, pos)
            return hbuf, cache, pos + adv, n_gen + adv, calls + 1

        def cond(state):
            return jnp.min(state[3]) < max_new

        hbuf, cache, pos, n_gen, calls = lax.while_loop(
            cond, body, (hbuf, cache, pos, n_gen, jnp.zeros((), jnp.int32))
        )
        return lax.dynamic_slice_in_dim(hbuf, t, max_new, axis=1), calls

    return run


def generate_speculative(
    model,
    params: dict,
    prompt: jax.Array,  # [b, t] int32
    max_new_tokens: int,
    window: int = 8,
    ngram: int = 2,
    return_calls: bool = False,
):
    """Greedy decode via prompt-lookup speculative decoding — tokens
    identical to ``model.generate(..., temperature=0)``, in fewer model
    calls whenever generated text revisits earlier spans.

    ``window`` — tokens scored per verify call (1 committed + window−1
    drafted); ``ngram`` — match length for the prompt lookup (2-3).
    ``return_calls=True`` also returns the number of verify iterations
    (the speedup diagnostic: plain greedy decode would be
    ``max_new_tokens`` calls).

    Requires ``t >= ngram`` and ``t + max_new_tokens + window <= max_seq``
    (the verify window of a just-finishing row must stay inside the
    cache)."""
    t = prompt.shape[1]
    model._check_generate_args(t, max_new_tokens, 0.0, 0, 0.0)
    if window < 2:
        raise ValueError(f"window must be >= 2 (1 real + >=1 draft), got {window}")
    if ngram < 1 or t < ngram:
        raise ValueError(f"need prompt_len ({t}) >= ngram ({ngram}) >= 1")
    if t + max_new_tokens + window > model.config.max_seq:
        raise ValueError(
            f"prompt ({t}) + max_new ({max_new_tokens}) + window ({window}) "
            f"must fit max_seq={model.config.max_seq} (the final verify "
            "window writes cache rows past the last emitted token)"
        )
    key = ("spec", t, max_new_tokens, window, ngram)
    cache = model._gen_cache_dict()
    run = cache.get(key)
    if run is None:
        run = jax.jit(_build_speculative_fn(model, t, max_new_tokens, window, ngram))
        cache[key] = run
    tokens, calls = run(params, prompt.astype(jnp.int32))
    if return_calls:
        return tokens, int(calls)
    return tokens
