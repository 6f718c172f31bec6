"""Paged int4 KV cache: page-pool serving, CoW prefixes, adaptive windows.

The paged cache must be a pure capacity optimization — at the same KV
codec, tokens are BIT-IDENTICAL whether rows live in a dense per-slot
cache or in pool pages behind a page table, no matter how pages were
allocated, shared copy-on-write, shipped in a handoff, or reclaimed. On
top sit the allocator's accounting invariants (no leaks, no writes into
shared pages) and the acceptance-aware speculative scheduling (window
width is pure scheduling: tokens identical at any width).
"""

import dataclasses
import gc

import jax.numpy as jnp
import numpy as np
import pytest

from dsml_tpu.models.gpt2 import GPT2, GPT2Config
from dsml_tpu.models.speculative import lookup_draft_batch, lookup_draft_host
from dsml_tpu.ops.quantization import (
    dequantize_kv_rows,
    kv_row_bytes,
    quantize_kv_rows,
)
from dsml_tpu.serving import ContinuousBatcher, build_fleet
from dsml_tpu.serving.paging import PagePool, pages_for, plan_admission


@pytest.fixture(scope="module")
def setup():
    cfg = GPT2Config.tiny()  # max_seq=128, n_head=8, d_model=64 -> hd=8
    model = GPT2(cfg)
    return cfg, model, model.init(0)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (l,)).astype(np.int32)
            for l in lengths]


def _drain_tokens(batcher, prompts, budgets):
    rids = [batcher.submit(p, n) for p, n in zip(prompts, budgets)]
    out = batcher.run()
    return [out[r] for r in rids]


# ---------------------------------------------------------------------------
# the int4/int8 page codec
# ---------------------------------------------------------------------------


def test_kv_row_codec_roundtrip_per_row_scales():
    """Round trip within each mode's quantization tolerance, one scale per
    row: scaling one row never perturbs another's bytes."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 16, 8)).astype(np.float32))
    for mode, qmax in (("int8", 127), ("int4", 7)):
        q, s = quantize_kv_rows(x, mode)
        back = dequantize_kv_rows(q, s, mode)
        # absmax symmetric quantization: error <= scale/2 per element
        assert float(jnp.max(jnp.abs(back - x) / s)) <= 0.5 + 1e-6
        # per-row independence: changing row 0 leaves every other row's
        # quantized bytes and scale bit-identical
        x2 = x.at[0, 0, :].multiply(3.0)
        q2, s2 = quantize_kv_rows(x2, mode)
        assert np.array_equal(np.asarray(q[1:]), np.asarray(q2[1:]))
        assert np.array_equal(np.asarray(s[1:]), np.asarray(s2[1:]))
        assert np.array_equal(np.asarray(q[0, 1:]), np.asarray(q2[0, 1:]))


def test_kv_row_codec_matches_dense_cache_quantizer(setup):
    """The dense cache's ``_kv_quantize`` IS the shared codec — identical
    bytes for identical rows (the gather-parity foundation)."""
    _, model, _ = setup
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 8, 5, 8)).astype(np.float32))
    for mode in ("int8", "int4"):
        q1, s1 = model._kv_quantize(x, mode)
        q2, s2 = quantize_kv_rows(x, mode)
        assert np.array_equal(np.asarray(q1), np.asarray(q2))
        assert np.array_equal(np.asarray(s1), np.asarray(s2))


def test_kv_row_codec_odd_tail_and_errors():
    rng = np.random.default_rng(2)
    # an odd number of ROWS (a partially filled tail page) is fine — only
    # the channel axis must be even for int4 nibble packing
    x = jnp.asarray(rng.standard_normal((7, 8)).astype(np.float32))
    q, s = quantize_kv_rows(x, "int4")
    assert q.shape == (7, 4) and s.shape == (7, 1)
    with pytest.raises(ValueError, match="even trailing"):
        quantize_kv_rows(jnp.zeros((4, 7)), "int4")
    with pytest.raises(ValueError, match="unknown KV quant"):
        quantize_kv_rows(x, "int2")
    # zero rows quantize to zeros with the safe scale 1.0 (no div-by-zero)
    qz, sz = quantize_kv_rows(jnp.zeros((3, 8)), "int4")
    assert np.array_equal(np.asarray(sz), np.ones((3, 1), np.float32))
    assert np.allclose(np.asarray(dequantize_kv_rows(qz, sz, "int4")), 0.0)


def test_kv_row_bytes_accounting():
    assert kv_row_bytes(64, None) == 256
    assert kv_row_bytes(64, "int8") == 68
    assert kv_row_bytes(64, "int4") == 36  # the ~7x dense-f32 ratio
    with pytest.raises(ValueError):
        kv_row_bytes(7, "int4")


def test_page_table_gather_parity_bitwise(setup):
    """THE gather parity pin: chunk-prefill the same prompt into a dense
    int4 cache and a paged pool (scattered page order on purpose) — every
    position's quantized bytes and scale are BIT-IDENTICAL, read back
    through the page table."""
    cfg, model, params = setup
    m4 = GPT2(dataclasses.replace(cfg, kv_quant="int4"))
    prompt = _prompts(cfg, [21], seed=3)[0]  # odd length: partial tail page
    c, page = 8, 8
    n_pt = cfg.max_seq // page

    cache1 = m4.init_cache(1)
    pool = model.init_page_pool(12, page, quant="int4")
    # deliberately non-contiguous physical pages for the logical rows
    pages = [5, 2, 9]
    table = np.zeros((1, n_pt), np.int32)
    table[0, : len(pages)] = pages
    for start in range(0, len(prompt), c):
        end = min(start + c, len(prompt))
        padded = np.zeros((1, c), np.int32)
        padded[0, : end - start] = prompt[start:end]
        last = (len(prompt) - 1) - start if end >= len(prompt) else c - 1
        lg_d, cache1 = m4.prefill_chunk(
            params, cache1, jnp.asarray(padded), jnp.int32(start),
            last_index=last,
        )
        lg_p, pool = model.prefill_chunk_paged(
            params, pool, jnp.asarray(table), jnp.asarray(padded),
            jnp.int32(start), last_index=last, quant="int4",
        )
    assert np.array_equal(np.asarray(lg_d), np.asarray(lg_p))
    for layer_d, layer_p in zip(cache1, pool):
        for key in ("k", "k_s", "v", "v_s"):
            dense = np.asarray(layer_d[key])[0]  # [H, max_seq, x]
            paged = np.asarray(layer_p[key])
            for pos in range(len(prompt)):
                phys, row = pages[pos // page], pos % page
                assert np.array_equal(dense[:, pos, :], paged[phys, :, row, :])


# ---------------------------------------------------------------------------
# allocator + CoW planner
# ---------------------------------------------------------------------------


def test_page_pool_accounting():
    pool = PagePool(8)  # 7 allocatable (page 0 = scratch)
    assert pool.free_pages == 7
    a = pool.alloc(3)
    assert 0 not in a and pool.used_pages == 3
    pool.share(a[:2])
    assert pool.shared_pages == 2
    pool.release(a)  # drops to refcount 1 on the shared two
    assert pool.used_pages == 2 and pool.shared_pages == 0
    pool.release(a[:2])
    assert pool.free_pages == 7
    assert pool.can_alloc(7) and not pool.can_alloc(8)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(8)
    with pytest.raises(RuntimeError, match="free/scratch"):
        pool.release([a[0]])  # double free
    with pytest.raises(RuntimeError, match="unowned"):
        pool.share([5])


def test_plan_admission_shapes():
    pool = PagePool(12)
    # no prefix: pure allocation
    plan = plan_admission(pool, 8, 20)
    assert len(plan.pages) == pages_for(20, 8) == 3
    assert plan.n_shared == 0 and plan.copy is None
    pool.release(plan.pages)

    prefix = pool.alloc(3)  # covers 20 prefix rows: 2 full + 1 straddle
    # page-aligned prefix share (16 rows): no copy
    p2 = plan_admission(pool, 8, 40, prefix_pages=prefix, prefix_len=16)
    assert p2.n_shared == 2 and p2.pages[:2] == prefix[:2] and p2.copy is None
    assert pool.refcount(prefix[0]) == 2
    pool.release(p2.pages)
    # straddling prefix (20 rows): share 2 full pages, COPY the third
    p3 = plan_admission(pool, 8, 40, prefix_pages=prefix, prefix_len=20)
    assert p3.n_shared == 2 and p3.copy == (prefix[2], p3.pages[2])
    pool.release(p3.pages)
    # share_prefix=False plans the same count with zero sharing
    p4 = plan_admission(pool, 8, 40, prefix_pages=prefix, prefix_len=20,
                        share_prefix=False)
    assert p4.n_shared == 0 and len(p4.pages) == 5
    pool.release(p4.pages)
    # insufficient pool -> None, and NOTHING was allocated or shared
    before = (pool.free_pages, pool.refcount(prefix[0]))
    assert plan_admission(pool, 8, 8 * (pool.free_pages + 1)) is None
    assert (pool.free_pages, pool.refcount(prefix[0])) == before


# ---------------------------------------------------------------------------
# paged batcher: token identity + capacity + CoW
# ---------------------------------------------------------------------------


def test_paged_batcher_matches_dense_same_codec(setup):
    """Paged int4 vs the dense batcher at the SAME codec (kv_quant=int4):
    greedy tokens bit-identical across staggered multi-request serving;
    paged fp vs the plain dense batcher pins the gather path alone."""
    cfg, model, params = setup
    m4 = GPT2(dataclasses.replace(cfg, kv_quant="int4"))
    prompts = _prompts(cfg, [5, 17, 32, 9, 26], seed=4)
    budgets = [5, 3, 6, 5, 3]

    ref4 = ContinuousBatcher(m4, params, n_slots=2, prefill_chunk=8)
    want4 = _drain_tokens(ref4, prompts, budgets)
    paged = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                              paged_kv="int4", page_size=8, n_pages=40)
    assert _drain_tokens(paged, prompts, budgets) == want4
    assert paged.free_pages == paged.n_pages - 1  # everything reclaimed

    ref = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8)
    want = _drain_tokens(ref, prompts, budgets)
    paged_fp = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                                 paged_kv="fp", page_size=8, n_pages=40)
    assert _drain_tokens(paged_fp, prompts, budgets) == want


def test_paged_batcher_temperature_matches_dense(setup):
    cfg, model, params = setup
    m4 = GPT2(dataclasses.replace(cfg, kv_quant="int4"))
    prompts = _prompts(cfg, [6, 14, 23], seed=5)
    kw = dict(n_slots=2, prefill_chunk=8, temperature=0.8, top_k=20, seed=7)
    ref = ContinuousBatcher(m4, params, **kw)
    want = _drain_tokens(ref, prompts, [4, 4, 4])
    paged = ContinuousBatcher(model, params, paged_kv="int4", page_size=8,
                              n_pages=40, **kw)
    assert _drain_tokens(paged, prompts, [4, 4, 4]) == want


def test_paged_capacity_backpressure_and_reuse(setup):
    """A pool too small for every request at once: admissions WAIT for
    pages (no deadlock, no preemption) and the drain completes with every
    token identical; a request that can never fit fails at submit."""
    cfg, model, params = setup
    m4 = GPT2(dataclasses.replace(cfg, kv_quant="int4"))
    prompts = _prompts(cfg, [30, 28, 25, 27], seed=6)
    ref = ContinuousBatcher(m4, params, n_slots=4, prefill_chunk=8)
    want = _drain_tokens(ref, prompts, [6] * 4)
    # 10 allocatable pages of 8 rows = 80 rows; each request reserves
    # ceil(32/8)+ pages -> only ~2 fit concurrently
    paged = ContinuousBatcher(model, params, n_slots=4, prefill_chunk=8,
                              paged_kv="int4", page_size=8, n_pages=11)
    assert _drain_tokens(paged, prompts, [6] * 4) == want
    assert paged.free_pages == 10
    with pytest.raises(ValueError, match="ever reservable"):
        paged.submit(_prompts(cfg, [100], seed=7)[0], 20)


def test_paged_pool_holds_4x_dense_concurrency_at_equal_hbm(setup):
    """At the dense f32 cache's HBM budget the int4 page pool buys >= 4x
    the rows (analytic), and a paged batcher sized to that budget really
    holds >= 4x the dense batcher's sequences in flight, with greedy
    tokens identical to the dense batcher at the same codec. Counts of
    slots and bytes, not timings."""
    cfg, model, params = setup
    m4 = GPT2(dataclasses.replace(cfg, kv_quant="int4"))
    hd = cfg.d_model // cfg.n_head
    n_dense, page_size = 2, 8
    per_row = cfg.n_layer * 2 * cfg.n_head
    budget = n_dense * per_row * cfg.max_seq * kv_row_bytes(hd, None)
    n_pages = budget // (per_row * page_size * kv_row_bytes(hd, "int4"))
    assert n_pages * page_size / (n_dense * cfg.max_seq) >= 4.0

    prompts = _prompts(cfg, [10, 33, 21, 15, 38, 12, 27, 19, 30, 11, 24, 36],
                       seed=12)

    def drain(batcher):
        rids = [batcher.submit(p, 24) for p in prompts]
        peak = 0
        while (batcher.n_queued or batcher.n_active or batcher.n_pending):
            batcher.step()
            peak = max(peak, batcher.n_active)
        out = batcher.collect()
        return [out[r] for r in rids], peak

    want, dense_peak = drain(ContinuousBatcher(
        m4, params, n_slots=n_dense, prefill_chunk=32))
    got, paged_peak = drain(ContinuousBatcher(
        model, params, n_slots=4 * n_dense, prefill_chunk=32,
        paged_kv="int4", page_size=page_size, n_pages=int(n_pages)))
    assert got == want
    assert dense_peak == n_dense
    assert paged_peak >= 4 * dense_peak


def test_never_fits_accounts_for_registry_pages(setup):
    """The never-fits checks subtract the prefix registry's permanent
    holdings (the code-review livelock: a pool mostly eaten by
    registrations must REJECT a too-big request at submit, not park it
    at the FIFO head forever) — and credit a matched prefix's shared
    pages, so matching requests still fit."""
    from dsml_tpu.serving import PrefillWorker

    cfg, model, params = setup
    rng = np.random.default_rng(16)
    prefix = rng.integers(1, cfg.vocab_size, 48).astype(np.int32)  # 6 pages
    srv = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=10)
    srv.register_prefix(prefix)  # 9 usable - 6 registry = 3 reservable
    with pytest.raises(ValueError, match="ever reservable"):
        srv.submit(rng.integers(1, cfg.vocab_size, 50).astype(np.int32), 10)
    # a PREFIX-MATCHING request rides the shared pages and fits
    rid = srv.submit(np.concatenate(
        [prefix, rng.integers(1, cfg.vocab_size, 6).astype(np.int32)]), 6)
    assert len(srv.run()[rid]) == 6

    pw = PrefillWorker(model, params, 8, paged_kv="int4", page_size=8,
                       n_pages=10)
    pw.register_prefix(prefix)
    with pytest.raises(ValueError, match="ever reservable"):
        pw.submit(rng.integers(1, cfg.vocab_size, 50).astype(np.int32), 4)
    # matching job fits (suffix grid only needs private pages past the
    # shared prefix)
    pw.submit(np.concatenate(
        [prefix, rng.integers(1, cfg.vocab_size, 6).astype(np.int32)]), 4)
    for _ in range(20):
        if pw.step():
            break
    else:
        raise AssertionError("matching prefill job did not complete")


def test_cow_prefix_pages_shared_and_reclaimed(setup):
    """Registered prefix = refcounted page-table entry: matching requests
    share its full pages read-only (used pages grow by far less than a
    full prefill's worth), the straddling tail page is copy-on-write
    materialized, tokens equal the no-prefix run, and retirement returns
    every request page (the registry's stay)."""
    cfg, model, params = setup
    rng = np.random.default_rng(7)
    prefix = rng.integers(1, cfg.vocab_size, 20).astype(np.int32)  # 2 full + straddle @ page 8
    tails = [rng.integers(1, cfg.vocab_size, 7).astype(np.int32)
             for _ in range(3)]
    prompts = [np.concatenate([prefix, t]) for t in tails]

    plain = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                              paged_kv="int4", page_size=8, n_pages=60)
    want = _drain_tokens(plain, prompts, [5] * 3)

    srv = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=60)
    srv.register_prefix(prefix)
    base_used = srv.used_pages
    assert base_used == pages_for(len(prefix), 8) == 3
    rids = [srv.submit(p, 5) for p in prompts]
    srv.step()
    # sharing is LIVE: the prefix's 2 full pages are multiply referenced,
    # and each admitted slot materialized its own straddle copy
    assert srv.shared_pages == 2
    assert srv.n_cow_copies >= 1
    out = srv.run()
    assert [out[r] for r in rids] == want
    assert srv.used_pages == base_used  # request pages reclaimed

    # exact-hit: the whole prompt is the prefix — zero prefill dispatches
    srv2 = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                             paged_kv="int4", page_size=8, n_pages=60)
    srv2.register_prefix(prefix)
    before = srv2.n_prefill_dispatches
    rid = srv2.submit(prefix, 4)
    out2 = srv2.run()
    assert srv2.n_prefill_dispatches - before < pages_for(len(prefix), 8)
    ref = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=60)
    r2 = ref.submit(prefix, 4)
    assert out2[rid] == ref.run()[r2]


def test_register_prefix_chunk_size_invariance(setup):
    """Quantized chunk chaining is chunk-size-invariant (every query
    reads every key quantized), so prefix pages registered with chunk 8
    match a worker prefilling at chunk 16 byte-for-byte — the property
    fleet-level CoW elision rests on."""
    cfg, model, params = setup
    rng = np.random.default_rng(8)
    prefix = rng.integers(1, cfg.vocab_size, 24).astype(np.int32)
    a = ContinuousBatcher(model, params, n_slots=1, prefill_chunk=8,
                          paged_kv="int4", page_size=8, n_pages=30)
    b = ContinuousBatcher(model, params, n_slots=1, prefill_chunk=16,
                          paged_kv="int4", page_size=8, n_pages=30)
    a.register_prefix(prefix)
    b.register_prefix(prefix)
    (_, pa, la), (_, pb, lb) = a._prefixes[0], b._prefixes[0]
    assert np.array_equal(la, lb)
    for layer_a, layer_b in zip(a._pool, b._pool):
        for key in layer_a:
            va = np.asarray(layer_a[key])[np.asarray(pa)]
            vb = np.asarray(layer_b[key])[np.asarray(pb)]
            # compare only the REAL prefix rows: the tail page's rows past
            # the prefix hold pad garbage, which differs by chunk grid
            flat_a = va.transpose(1, 0, 2, 3).reshape(va.shape[1], -1, va.shape[3])
            flat_b = vb.transpose(1, 0, 2, 3).reshape(vb.shape[1], -1, vb.shape[3])
            assert np.array_equal(flat_a[:, : len(prefix)], flat_b[:, : len(prefix)])


def test_paged_constructor_validation(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError, match="divide max_seq"):
        ContinuousBatcher(model, params, paged_kv="int4", page_size=7,
                          prefill_chunk=8)
    with pytest.raises(ValueError, match="turbo_factor"):
        ContinuousBatcher(model, params, paged_kv="int4", page_size=8,
                          prefill_chunk=8, turbo_factor=2)
    with pytest.raises(ValueError, match="page quant"):
        ContinuousBatcher(model, params, paged_kv="int3", page_size=8,
                          prefill_chunk=8)
    srv = ContinuousBatcher(model, params, paged_kv="int4", page_size=8)
    with pytest.raises(ValueError, match="prefill_chunk"):
        srv.submit(np.asarray([1, 2, 3], np.int32), 2)


# ---------------------------------------------------------------------------
# speculative: acceptance EWMAs + adaptive window
# ---------------------------------------------------------------------------


def test_paged_speculative_matches_dense_and_generate(setup):
    cfg, model, params = setup
    m4 = GPT2(dataclasses.replace(cfg, kv_quant="int4"))
    rng = np.random.default_rng(9)
    prompts = [np.tile(rng.integers(1, 50, 6).astype(np.int32), 3)
               for _ in range(3)]
    ref = ContinuousBatcher(m4, params, n_slots=2, prefill_chunk=8,
                            speculative_window=4)
    want = _drain_tokens(ref, prompts, [10] * 3)
    paged = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                              speculative_window=4, paged_kv="int4",
                              page_size=8, n_pages=40)
    assert _drain_tokens(paged, prompts, [10] * 3) == want
    assert paged.accept_ewma is not None and 0.0 <= paged.accept_ewma <= 1.0
    assert paged.predicted_tpot_s() is not None
    assert paged.free_pages == paged.n_pages - 1


def test_adaptive_window_same_tokens_any_width(setup):
    """Window width is pure scheduling: the adaptive batcher's tokens
    equal the fixed-window batcher's, and the width choice is the
    documented monotone map of the acceptance EWMA."""
    cfg, model, params = setup
    rng = np.random.default_rng(10)
    prompts = [np.tile(rng.integers(1, 50, 5).astype(np.int32), 4)
               for _ in range(3)]
    fixed = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                              speculative_window=6, paged_kv="int4",
                              page_size=8, n_pages=40)
    want = _drain_tokens(fixed, prompts, [12] * 3)
    adaptive = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                                 speculative_window=6,
                                 speculative_adaptive=True, paged_kv="int4",
                                 page_size=8, n_pages=40)
    assert _drain_tokens(adaptive, prompts, [12] * 3) == want
    assert sum(adaptive.spec_window_used.values()) == adaptive.n_spec_ticks

    # white-box: the width map across acceptance regimes (optimistic max
    # before the first measurement; floor 2 at zero acceptance; the
    # configured max at full acceptance; monotone between)
    srv = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                            speculative_window=8, speculative_adaptive=True,
                            paged_kv="int4", page_size=8, n_pages=40)
    assert srv._spec_window_for_tick() == 8  # no measurement yet
    widths = []
    for acc in (0.0, 0.25, 0.5, 0.75, 1.0):
        srv.accept_ewma = acc
        widths.append(srv._spec_window_for_tick())
    assert widths[0] == 2 and widths[-1] == 8
    assert widths == sorted(widths)

    with pytest.raises(ValueError, match="speculative_adaptive"):
        ContinuousBatcher(model, params, speculative_adaptive=True)


def test_acceptance_ewma_updates_and_censoring(setup):
    """A retirement mid-window censors the acceptance sample (unconsumed
    drafts were never judged) unless the window fully accepted."""
    cfg, model, params = setup
    srv = ContinuousBatcher(model, params, n_slots=1, prefill_chunk=8,
                            speculative_window=4, paged_kv="int4",
                            page_size=8, n_pages=40)
    rng = np.random.default_rng(11)
    srv.submit(np.tile(rng.integers(1, 50, 4).astype(np.int32), 3), 2)
    srv.run()  # budget 2 < window 4: first window retires mid-flight
    # either censored (None) or a full-acceptance sample — never a biased
    # partial-window rate
    assert srv.accept_ewma in (None, 1.0)


# ---------------------------------------------------------------------------
# the host/device draft rule (satellite: one shared helper)
# ---------------------------------------------------------------------------


def test_lookup_draft_host_rules():
    h = np.asarray([1, 2, 3, 9, 1, 2, 3, 7, 1, 2], np.int32)
    # trailing 2-gram [1, 2] most recently recurs at index 4 -> [3, 7, 1]
    assert list(lookup_draft_host(h, 2, 3)) == [3, 7, 1]
    # no match -> repeat last token
    assert list(lookup_draft_host(np.asarray([5, 6, 7], np.int32), 2, 2)) == [7, 7]
    # match so close to the end the draft runs out -> pad with last token
    h2 = np.asarray([4, 4, 1, 2, 4, 4], np.int32)
    assert list(lookup_draft_host(h2, 2, 4)) == [1, 2, 4, 4]


def test_lookup_draft_host_equals_device():
    """The batcher's host rule and the jitted speculator's device rule are
    THE SAME rule: equal drafts over random histories at interior
    positions (the device buffer's fixed shape needs pos < max_seq)."""
    rng = np.random.default_rng(12)
    max_seq, n, w = 64, 2, 5
    for trial in range(8):
        length = int(rng.integers(8, 40))
        hist = rng.integers(0, 6, length).astype(np.int32)  # small vocab: matches happen
        hbuf = np.zeros((1, max_seq), np.int32)
        hbuf[0, :length] = hist
        dev = np.asarray(lookup_draft_batch(
            jnp.asarray(hbuf), jnp.asarray([length - 1], np.int32), w, n
        ))[0]
        host = lookup_draft_host(hist, n, w - 1)
        assert np.array_equal(dev, host), (trial, hist)


# ---------------------------------------------------------------------------
# fleet: paged handoffs + decode-side CoW + metrics
# ---------------------------------------------------------------------------


def test_paged_fleet_matches_monolithic(setup):
    """Paged disaggregated fleet ≡ monolithic paged batcher, including
    prefix-eliding handoffs (decode workers share their own registered
    prefix pages) and the CRC-framed wire codec."""
    from dsml_tpu.serving.handoff import frame_transport

    cfg, model, params = setup
    rng = np.random.default_rng(13)
    prefix = rng.integers(1, cfg.vocab_size, 20).astype(np.int32)
    prompts = []
    for i in range(6):
        if i % 2:
            prompts.append(np.concatenate(
                [prefix, rng.integers(1, cfg.vocab_size,
                                      int(rng.integers(3, 10))).astype(np.int32)]))
        else:
            prompts.append(rng.integers(1, cfg.vocab_size,
                                        int(rng.integers(5, 25))).astype(np.int32))

    mono = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                             paged_kv="int4", page_size=8, n_pages=80)
    mono.register_prefix(prefix)
    want = _drain_tokens(mono, prompts, [6] * 6)

    for transport in (None, frame_transport):
        router = build_fleet(model, params, n_prefill=2, n_decode=2,
                             prefill_chunk=8, paged_kv="int4", page_size=8,
                             n_slots=2, n_pages=80, transport=transport)
        router.register_prefix(prefix)
        frids = [router.submit(p, 6) for p in prompts]
        out = router.run()
        assert [out[f] for f in frids] == want, transport
        # prefix elision was active: prefill workers ship suffix pages only
        assert all(pw.ship_prefix_pages for pw in router.prefill_workers)
        # decode pools hold exactly their registry pages again
        for dw in router.decode_workers:
            assert dw.used_pages == pages_for(len(prefix), 8)


def test_paged_handoff_codec_roundtrip(setup):
    """encode/decode preserves a paged handoff bit-exactly: page payload,
    page_size, prefix_rows."""
    from dsml_tpu.serving.handoff import Handoff, decode_handoff, encode_handoff

    cfg, model, params = setup
    rng = np.random.default_rng(14)
    pages = [
        {"k": rng.integers(0, 255, (3, 8, 8, 4)).astype(np.uint8),
         "k_s": rng.standard_normal((3, 8, 8, 1)).astype(np.float32),
         "v": rng.integers(0, 255, (3, 8, 8, 4)).astype(np.uint8),
         "v_s": rng.standard_normal((3, 8, 8, 1)).astype(np.float32)}
        for _ in range(cfg.n_layer)
    ]
    h = Handoff(frid=7, prompt=np.asarray([1, 2, 3], np.int32),
                max_new_tokens=4, prefill_len=3, cache1=pages,
                logits=rng.standard_normal(cfg.vocab_size).astype(np.float32),
                page_size=8, prefix_rows=16)
    back = decode_handoff(encode_handoff(h))
    assert back.page_size == 8 and back.prefix_rows == 16
    for la, lb in zip(h.cache1, back.cache1):
        for key in la:
            assert np.array_equal(la[key], lb[key])


def test_paged_inject_validation(setup):
    cfg, model, params = setup
    srv = ContinuousBatcher(model, params, n_slots=2, paged_kv="int4",
                            page_size=8, n_pages=40)
    prompt = np.asarray([1, 2, 3], np.int32)
    logits = np.zeros(cfg.vocab_size, np.float32)
    pages = [{key: np.zeros((1, *np.asarray(arr).shape[1:]),
                            np.asarray(arr).dtype)
              for key, arr in layer.items()} for layer in srv._pool]
    with pytest.raises(ValueError, match="kv_pages"):
        srv.inject(prompt, 2, [{}] * cfg.n_layer, logits)  # dense into paged
    with pytest.raises(ValueError, match="page size"):
        srv.inject(prompt, 2, logits_row=logits, kv_pages=pages, page_size=16)
    with pytest.raises(ValueError, match="prefix_rows"):
        srv.inject(prompt, 2, logits_row=logits, kv_pages=pages, page_size=8,
                   prefix_rows=5)  # not a page multiple
    with pytest.raises(RuntimeError, match="no registered prefix"):
        srv.inject(np.arange(1, 20, dtype=np.int32), 2, logits_row=logits,
                   kv_pages=pages, page_size=8, prefix_rows=8)
    # mixed fleets rejected at the router edge
    from dsml_tpu.serving import PrefillWorker, Router

    dense_pw = PrefillWorker(model, params, 8)
    with pytest.raises(ValueError, match="mixed fleet"):
        Router([dense_pw], [srv])


# ---------------------------------------------------------------------------
# eviction-based preemption (preemption=True)
# ---------------------------------------------------------------------------


def _pressure_prompts(cfg, seed=20):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
               for l in (17, 9, 13)]
    return prompts, [12, 12, 10]


@pytest.mark.parametrize("policy", ["auto", "swap", "recompute"])
def test_preemption_under_pressure_identical_tokens(setup, policy):
    """The eviction tier is pure scheduling: a pool far too small for the
    worst case admits against CURRENT demand, evicts under decode-growth
    pressure, resumes the victim, and every request's tokens equal the
    big-pool run — with zero pages leaked."""
    cfg, model, params = setup
    prompts, budgets = _pressure_prompts(cfg)
    ref = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=40)
    want = _drain_tokens(ref, prompts, budgets)
    srv = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=8,
                            preemption=True, preempt_policy=policy)
    assert _drain_tokens(srv, prompts, budgets) == want
    assert srv.n_preemptions > 0  # the pressure leg actually ran
    if policy == "swap":
        assert srv.n_swap_evictions == srv.n_preemptions
    elif policy == "recompute":
        assert srv.n_recompute_evictions == srv.n_preemptions
    assert srv.free_pages == srv.n_pages - 1  # no leak
    assert srv.n_preempted == 0  # every victim resumed and retired


def test_preemption_speculative_identical_tokens(setup):
    """Verify-window growth rides the same eviction tier: speculative
    decode under page pressure emits the no-pressure run's tokens."""
    cfg, model, params = setup
    rng = np.random.default_rng(21)
    prompts = [np.tile(rng.integers(1, 50, 6).astype(np.int32), 3)
               for _ in range(3)]
    ref = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                            speculative_window=4, paged_kv="int4",
                            page_size=8, n_pages=40)
    want = _drain_tokens(ref, prompts, [10] * 3)
    srv = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                            speculative_window=4, paged_kv="int4",
                            page_size=8, n_pages=9, preemption=True)
    assert _drain_tokens(srv, prompts, [10] * 3) == want
    assert srv.free_pages == srv.n_pages - 1


def test_preemption_victim_order_priority_then_youngest(setup):
    """The eviction order: lowest priority first, youngest rid within a
    priority, the growing slot shielded via ``exclude`` until it is the
    only one left."""
    cfg, model, params = setup
    srv = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=20,
                            preemption=True)
    srv._slot_rid[:] = [5, 6, 7]
    srv._slot_prio[:] = [1, 0, 0]
    # priorities (1, 0, 0): slot 2 (prio 0, youngest rid 7) goes first
    assert srv._pick_victim() == 2
    assert srv._pick_victim(exclude=2) == 1
    srv._slot_rid[:] = [5, -1, -1]
    assert srv._pick_victim(exclude=0) is None  # nothing else holds pages
    srv._slot_rid[:] = -1


def test_preemption_priority_protects_high_priority_slot(setup):
    """Under pressure the LOW-priority request is the one evicted; the
    high-priority request decodes through without a single preemption —
    and both finish with the reference tokens."""
    cfg, model, params = setup
    prompts, budgets = _pressure_prompts(cfg)
    ref = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=40)
    want = _drain_tokens(ref, prompts, budgets)

    srv = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=8,
                            preemption=True)
    evicted = []
    orig = srv._evict_slot

    def spy(slot):
        evicted.append(int(srv._slot_rid[slot]))
        orig(slot)

    srv._evict_slot = spy
    rids = [srv.submit(p, n, priority=(10 if i == 0 else 0))
            for i, (p, n) in enumerate(zip(prompts, budgets))]
    out = srv.run()
    assert [out[r] for r in rids] == want
    assert evicted and rids[0] not in evicted  # priority 10 never evicted


def test_preemption_never_evicts_shared_cow_pages(setup):
    """A CoW-shared prefix page is NEVER swapped or freed while shared:
    eviction only drops the victim's reference — the registry master
    survives every preemption storm byte-intact, and matching requests
    keep sharing it."""
    cfg, model, params = setup
    rng = np.random.default_rng(22)
    prefix = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)  # 2 pages
    tails = [rng.integers(1, cfg.vocab_size, 5).astype(np.int32)
             for _ in range(3)]
    prompts = [np.concatenate([prefix, t]) for t in tails]

    plain = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                              paged_kv="int4", page_size=8, n_pages=40)
    plain.register_prefix(prefix)
    want = _drain_tokens(plain, prompts, [10] * 3)

    srv = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=8,
                            preemption=True)
    srv.register_prefix(prefix)
    reg_pages = list(srv._prefixes[0][1])
    master = [{key: np.asarray(arr[np.asarray(reg_pages)])
               for key, arr in layer.items()} for layer in srv._pool]
    rids = [srv.submit(p, 10) for p in prompts]
    min_ref = 10 ** 9
    out = {}
    while srv.n_active or srv.n_queued or srv.n_pending or srv.n_preempted:
        out.update(srv.step())
        # the registry's own reference never drops, evictions included
        min_ref = min(min_ref, *(srv._pages.refcount(p)
                                 for p in reg_pages[:2]))
    out.update(srv.collect())
    assert [out[r] for r in rids] == want
    assert srv.n_preemptions > 0
    assert min_ref >= 1  # master reference held throughout
    for layer, m in zip(srv._pool, master):
        for key in m:  # registry bytes untouched by the storm
            assert np.array_equal(np.asarray(layer[key][np.asarray(reg_pages)]), m[key])
    assert srv.used_pages == len(reg_pages)  # only the registry stays


def test_preemption_constructor_and_submit_validation(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError, match="paged_kv"):
        ContinuousBatcher(model, params, preemption=True)
    with pytest.raises(ValueError, match="preempt_policy"):
        ContinuousBatcher(model, params, paged_kv="int4", page_size=8,
                          prefill_chunk=8, preemption=True,
                          preempt_policy="drop")
    # the never-fits check stays WORST-CASE under preemption: eviction
    # cannot shrink one request's own eventual footprint
    srv = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=10,
                            preemption=True)
    with pytest.raises(ValueError, match="ever reservable"):
        srv.submit(np.arange(1, 100, dtype=np.int32), 20)


def test_preemption_pipelined_kernel_chaos_smoke(setup, monkeypatch):
    """The kernel-fusion chaos leg (ISSUE 16): eviction-based preemption
    under pool pressure with the DOUBLE-BUFFERED Pallas kernel enabled —
    resumed victims re-emit tokens identical to the uncontended XLA-path
    run, and the page-pool ledger returns to its byte-exact idle
    baseline (all pages free + the scratch page, zero live/shared
    bytes). The DMA slot ring must not leak state across an eviction:
    a resumed slot's pages land elsewhere in the pool and the kernel
    walk restarts from the table, not from stale scratch."""
    from dsml_tpu.obs.memory import get_memory_ledger

    cfg, model, params = setup
    prompts, budgets = _pressure_prompts(cfg)
    monkeypatch.setenv("DSML_PAGED_ATTN", "xla")
    ref = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=40)
    want = _drain_tokens(ref, prompts, budgets)
    del ref  # its WeakMethod ledger source must not pollute the claim sum
    gc.collect()

    monkeypatch.setenv("DSML_PAGED_ATTN", "pallas")
    monkeypatch.setenv("DSML_PAGED_ATTN_PIPELINE", "1")
    srv = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=8,
                            preemption=True)
    baseline = srv._ledger_page_bytes()
    assert baseline["live"] == baseline["shared"] == 0  # idle pool
    assert _drain_tokens(srv, prompts, budgets) == want
    assert srv.n_preemptions > 0  # the pressure leg actually evicted
    assert srv.n_preempted == 0  # every victim resumed and retired
    assert srv._ledger_page_bytes() == baseline  # byte-exact return
    # the registered ledger source reports the same baseline split
    claimed = get_memory_ledger(srv._obs).claimed().get("kv_pages", {})
    if claimed:  # observability may be disabled in the default suite
        assert sum(claimed.values()) == sum(baseline.values())


def test_preemption_fleet_injected_slot_keeps_cow_boundary(setup):
    """The inject path (paged handoff admission) must record the CoW
    boundary too: an injected slot's shared prefix pages are
    reference-only, so a later eviction drops the reference instead of
    swapping registry pages out as if they were private — fleet +
    preemption drains with reference tokens, preemptions exercised, and
    every decode pool back to exactly its registry pages."""
    cfg, model, params = setup
    rng = np.random.default_rng(30)
    prefix = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)  # 2 pages
    prompts = [np.concatenate(
        [prefix, rng.integers(1, cfg.vocab_size, 5).astype(np.int32)])
        for _ in range(3)]
    mono = ContinuousBatcher(model, params, n_slots=3, prefill_chunk=8,
                             paged_kv="int4", page_size=8, n_pages=60)
    mono.register_prefix(prefix)
    want = _drain_tokens(mono, prompts, [10] * 3)

    router = build_fleet(model, params, n_prefill=1, n_decode=1,
                         prefill_chunk=8, paged_kv="int4", page_size=8,
                         n_slots=3, n_pages=8, preemption=True)
    router.register_prefix(prefix)
    dw = router.decode_workers[0]
    frids = [router.submit(p, 10) for p in prompts]
    saw_shared_inject = 0
    ticks = 0
    while router.outstanding:
        router.tick()
        # white-box: every occupied slot admitted via inject carries its
        # shared-page count (the eviction tier's CoW boundary)
        for s in np.flatnonzero(dw._slot_rid >= 0):
            saw_shared_inject = max(saw_shared_inject,
                                    int(dw._slot_shared[int(s)]))
        ticks += 1
        assert ticks < 100_000, "fleet did not drain under preemption"
    out = router.run(max_ticks=1)
    assert [out[f] for f in frids] == want
    assert dw.n_preemptions > 0  # an injected slot really was evicted
    assert saw_shared_inject == 2  # the boundary rode the inject path
    assert dw.used_pages == pages_for(len(prefix), 8)  # registry only


# ---------------------------------------------------------------------------
# TP-sharded page pool (mesh= composes with paged_kv)
# ---------------------------------------------------------------------------


def test_tp2_paged_batcher_matches_single_device(setup, devices8):
    """mesh= shards the page pool's HEAD axis over tp: tokens identical
    to the single-device paged batcher (and so to dense), each chip
    holding 1/tp of every page — the capacity win lands per chip."""
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg, model, params = setup
    prompts = _prompts(cfg, [5, 17, 32, 9], seed=23)
    budgets = [5, 3, 6, 5]
    ref = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=40)
    want = _drain_tokens(ref, prompts, budgets)

    mesh = build_mesh(MeshSpec(tp=2), devices8[:2])
    srv = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                            paged_kv="int4", page_size=8, n_pages=40,
                            mesh=mesh)
    assert _drain_tokens(srv, prompts, budgets) == want
    assert srv.free_pages == srv.n_pages - 1
    # the pool is genuinely head-sharded: each chip holds H/tp heads of
    # every page — per-chip pool bytes are 1/tp of the global pool
    shard = srv._pool[0]["k"].addressable_shards[0]
    assert shard.data.shape[1] == cfg.n_head // 2
    assert shard.data.shape[0] == srv.n_pages  # page axis replicated

    with pytest.raises(ValueError, match="divisible by tp"):
        ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                          paged_kv="int4", page_size=8, n_pages=40,
                          mesh=build_mesh(MeshSpec(tp=3), devices8[:3]))


def test_tp2_paged_fleet_matches_monolithic(setup, devices8):
    """The acceptance leg: a paged fleet whose decode workers each carry
    tp=2 (``build_fleet(devices=...)``) drains with tokens identical to
    the monolithic single-device paged batcher, prefix elision live."""
    cfg, model, params = setup
    rng = np.random.default_rng(24)
    prefix = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
    prompts = []
    for i in range(4):
        tail = rng.integers(1, cfg.vocab_size,
                            int(rng.integers(3, 10))).astype(np.int32)
        prompts.append(np.concatenate([prefix, tail]) if i % 2 else
                       rng.integers(1, cfg.vocab_size,
                                    int(rng.integers(5, 20))).astype(np.int32))
    mono = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                             paged_kv="int4", page_size=8, n_pages=60)
    mono.register_prefix(prefix)
    want = _drain_tokens(mono, prompts, [5] * 4)

    router = build_fleet(model, params, n_prefill=1, n_decode=2,
                         prefill_chunk=8, paged_kv="int4", page_size=8,
                         n_slots=2, n_pages=60, devices=devices8[:4])
    router.register_prefix(prefix)
    frids = [router.submit(p, 5) for p in prompts]
    out = router.run()
    assert [out[f] for f in frids] == want
    for dw in router.decode_workers:
        assert dw.mesh is not None and dw.mesh.shape["tp"] == 2
        assert dw.used_pages == pages_for(len(prefix), 8)


def test_tp2_paged_capacity_ratio_per_chip(setup):
    """The ≥4× capacity story survives TP: at the dense f32 cache's
    per-chip HBM budget, the int4 page pool's per-chip rows (heads/tp of
    every page) hold ≥4× the sequences (analytic accounting)."""
    cfg, model, params = setup
    hd = cfg.d_model // cfg.n_head
    tp = 2
    page_size = 8
    # per-chip bytes of ONE dense f32 slot vs ONE int4 page (both carry
    # n_head/tp heads per chip)
    dense_slot = cfg.n_layer * 2 * (cfg.n_head // tp) * cfg.max_seq \
        * kv_row_bytes(hd, None)
    page = cfg.n_layer * 2 * (cfg.n_head // tp) * page_size \
        * kv_row_bytes(hd, "int4")
    n_dense_slots = 4
    budget = n_dense_slots * dense_slot
    rows_at_budget = (budget // page) * page_size
    assert rows_at_budget / (n_dense_slots * cfg.max_seq) >= 4.0


# ---------------------------------------------------------------------------
# metrics: scrape-time collect hook
# ---------------------------------------------------------------------------


def test_page_pool_gauges_fresh_at_scrape_without_ticks(setup):
    """The fix: pool gauges export at SCRAPE time (collect hook), not per
    tick — occupancy changes between ticks (here: a prefix registration
    with zero ``step()`` calls) show up at the next collect instead of
    freezing at the last tick's values."""
    from dsml_tpu import obs
    from dsml_tpu.serving import PrefillWorker

    cfg, model, params = setup
    obs.enable(forensics=False)
    try:
        srv = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                                paged_kv="int4", page_size=8, n_pages=40)
        pw = PrefillWorker(model, params, 8, paged_kv="int4", page_size=8,
                           n_pages=20)

        def scrape(role):
            return {r["name"]: r["value"]
                    for r in obs.get_registry().collect()
                    if r["name"].startswith("serving_page_pool")
                    and r["labels"].get("role") == role}

        # no tick has EVER run: the hook still exports current occupancy
        assert scrape("decode")["serving_page_pool_free"] == srv.free_pages
        assert scrape("prefill")["serving_page_pool_free"] == \
            pw._pages.free_pages
        before = scrape("decode")["serving_page_pool_used"]
        rng = np.random.default_rng(25)
        srv.register_prefix(rng.integers(1, cfg.vocab_size, 24).astype(np.int32))
        pw.register_prefix(rng.integers(1, cfg.vocab_size, 16).astype(np.int32))
        after = scrape("decode")
        # occupancy moved with ZERO ticks in between — per-tick export
        # would still show `before`
        assert after["serving_page_pool_used"] == before + 3 == srv.used_pages
        assert scrape("prefill")["serving_page_pool_used"] == \
            pw._pages.used_pages
    finally:
        obs.disable()


def test_page_pool_metrics_exported(setup):
    """Satellite: pool occupancy/free-list/acceptance gauges land in the
    metrics registry with (replica, role) labels."""
    from dsml_tpu import obs

    cfg, model, params = setup
    obs.enable(forensics=False)
    try:
        srv = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                                speculative_window=4, paged_kv="int4",
                                page_size=8, n_pages=40)
        srv.obs_replica = "3"
        rng = np.random.default_rng(15)
        srv.submit(np.tile(rng.integers(1, 50, 4).astype(np.int32), 4), 6)
        srv.run()
        rows = {(r["name"], r["labels"].get("replica"), r["labels"].get("role")): r["value"]
                for r in obs.get_registry().collect()
                if r["name"].startswith(("serving_page_pool", "serving_spec"))}
        for name in ("serving_page_pool_used", "serving_page_pool_free",
                     "serving_spec_accept_rate"):
            assert (name, "3", "decode") in rows, (name, sorted(rows))
        assert rows[("serving_page_pool_free", "3", "decode")] == srv.free_pages
    finally:
        obs.disable()
