"""Pallas paged-attention decode kernel: gather-free page-table reads.

The kernel (``ops/paged_attention.py``) must be a pure TRAFFIC
optimization — numerically equal to the XLA gather path (which stays the
fallback and the oracle) over every page-table shape the batcher can
produce: scattered/permuted physical pages, odd straddling tail pages,
CoW-shared prefix pages, all three pool codecs, GQA grouping, and the
multi-row verify window. Greedy tokens through the full
``decode_step_slots_paged`` surface are BIT-identical between the two
implementations, and the analytic HBM accounting scales with LIVE pages
under the kernel vs the pool-table shape under the gather.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dsml_tpu.models.gpt2 import GPT2, GPT2Config
from dsml_tpu.models.llama import Llama, LlamaConfig
from dsml_tpu.ops.paged_attention import (
    paged_attention,
    paged_attn_impl,
    paged_hbm_bytes,
)
from dsml_tpu.ops.quantization import dequantize_kv_rows, quantize_kv_rows


@pytest.fixture(scope="module")
def setup():
    cfg = GPT2Config.tiny()  # max_seq=128, n_head=8, d_model=64 -> hd=8
    model = GPT2(cfg)
    return cfg, model, model.init(0)


# ---------------------------------------------------------------------------
# direct kernel parity vs an independent dense oracle
# ---------------------------------------------------------------------------


def _make_pool_layer(rng, n_pages, hkv, page_size, hd, mode):
    """One layer's pool entry with random rows, in ``init_page_pool``'s
    exact layout (int4 nibbles packed, one f32 scale per row)."""
    k = rng.standard_normal((n_pages, hkv, page_size, hd)).astype(np.float32)
    v = rng.standard_normal((n_pages, hkv, page_size, hd)).astype(np.float32)
    if mode is None:
        return {"k": jnp.asarray(k), "v": jnp.asarray(v)}, k, v
    kq, ks = quantize_kv_rows(jnp.asarray(k), mode)
    vq, vs = quantize_kv_rows(jnp.asarray(v), mode)
    layer = {"k": kq, "k_s": ks, "v": vq, "v_s": vs}
    # the oracle sees exactly what the kernel can reconstruct: the
    # DEQUANTIZED rows (codec round-trip error is shared, not tolerated)
    k = np.asarray(dequantize_kv_rows(kq, ks, mode))
    v = np.asarray(dequantize_kv_rows(vq, vs, mode))
    return layer, k, v


def _oracle(q, k_pool, v_pool, table, positions, page_size):
    """Dense reference: gather pages per table, repeat kv heads over the
    query group, mask ``key_pos <= query_pos``, plain f64 softmax."""
    b, hq, c, hd = q.shape
    hkv = k_pool.shape[1]
    rep = hq // hkv
    n_pt = table.shape[1]
    s = n_pt * page_size
    out = np.zeros((b, hq, c, hd))
    key_pos = np.arange(s)
    for bi in range(b):
        # [n_pt, hkv, page, hd] -> [hkv, S, hd]
        kd = k_pool[table[bi]].transpose(1, 0, 2, 3).reshape(hkv, s, hd)
        vd = v_pool[table[bi]].transpose(1, 0, 2, 3).reshape(hkv, s, hd)
        for h in range(hq):
            scores = (q[bi, h].astype(np.float64) @ kd[h // rep].T.astype(np.float64)
                      ) * hd ** -0.5
            mask = key_pos[None, :] <= positions[bi][:, None]
            scores = np.where(mask, scores, -np.inf)
            p = np.exp(scores - scores.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[bi, h] = p @ vd[h // rep].astype(np.float64)
    return out.astype(np.float32)


@pytest.mark.parametrize("mode", [None, "int8", "int4"])
def test_kernel_parity_scattered_pages_all_codecs(mode):
    """Decode (C=1) over deliberately permuted physical pages with per-slot
    depths that straddle page boundaries (odd tails), dead entries at the
    scratch page — kernel ≡ dense oracle for every codec."""
    rng = np.random.default_rng(0)
    n_pages, hkv, page, hd = 12, 2, 8, 8
    layer, k, v = _make_pool_layer(rng, n_pages, hkv, page, hd, mode)
    # three slots: depths 21 (straddles page 3), 8 (exactly one page), 1
    table = np.zeros((3, 4), np.int32)
    table[0, :3] = [7, 2, 10]  # scattered, non-monotonic
    table[1, :1] = [5]
    table[2, :1] = [9]
    positions = np.asarray([[20], [7], [0]], np.int32)
    q = rng.standard_normal((3, 2, 1, hd)).astype(np.float32)

    got = np.asarray(paged_attention(
        jnp.asarray(q), layer, jnp.asarray(table), jnp.asarray(positions),
        mode, interpret=True,
    ))
    want = _oracle(q, k, v, table, positions, page)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_kernel_parity_gqa_grouped_heads():
    """GQA: 8 query heads over 2 kv heads (rep=4, the Llama grouping rule
    ``h // rep``) — one grid step scores a kv head's whole query group."""
    rng = np.random.default_rng(1)
    layer, k, v = _make_pool_layer(rng, 10, 2, 8, 8, "int4")
    table = np.zeros((2, 4), np.int32)
    table[0, :2] = [3, 8]
    table[1, :3] = [6, 1, 4]
    positions = np.asarray([[13], [22]], np.int32)
    q = rng.standard_normal((2, 8, 1, 8)).astype(np.float32)
    got = np.asarray(paged_attention(
        jnp.asarray(q), layer, jnp.asarray(table), jnp.asarray(positions),
        "int4", interpret=True,
    ))
    want = _oracle(q, k, v, table, positions, 8)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_kernel_parity_verify_window_rows():
    """C>1 (the speculative verify window): per-row causal positions —
    row j of the window attends through position ``start+j``."""
    rng = np.random.default_rng(2)
    layer, k, v = _make_pool_layer(rng, 10, 2, 8, 8, "int8")
    table = np.zeros((2, 4), np.int32)
    table[0, :3] = [2, 9, 5]
    table[1, :2] = [7, 3]
    start = np.asarray([17, 9], np.int32)
    positions = start[:, None] + np.arange(4)[None, :]
    q = rng.standard_normal((2, 4, 4, 8)).astype(np.float32)
    got = np.asarray(paged_attention(
        jnp.asarray(q), layer, jnp.asarray(table), jnp.asarray(positions),
        "int8", interpret=True,
    ))
    want = _oracle(q, k, v, table, positions.astype(np.int32), 8)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_kernel_parity_cow_shared_pages():
    """Two slots' tables naming the SAME physical prefix pages (CoW
    sharing): both read the shared rows correctly — page reads are pure,
    so multiply-referenced pages need no special casing in the kernel."""
    rng = np.random.default_rng(3)
    layer, k, v = _make_pool_layer(rng, 10, 2, 8, 8, "int4")
    shared = [4, 6]  # both slots' first 16 rows
    table = np.zeros((2, 4), np.int32)
    table[0, :3] = shared + [2]
    table[1, :3] = shared + [8]
    positions = np.asarray([[18], [21]], np.int32)
    q = rng.standard_normal((2, 2, 1, 8)).astype(np.float32)
    got = np.asarray(paged_attention(
        jnp.asarray(q), layer, jnp.asarray(table), jnp.asarray(positions),
        "int4", interpret=True,
    ))
    want = _oracle(q, k, v, table, positions, 8)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_kernel_validation_errors():
    rng = np.random.default_rng(4)
    layer, _, _ = _make_pool_layer(rng, 4, 2, 8, 8, None)
    q = jnp.zeros((1, 2, 1, 8))
    t = jnp.zeros((1, 2), jnp.int32)
    p = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="unknown page quant mode"):
        paged_attention(q, layer, t, p, "int2", interpret=True)
    with pytest.raises(ValueError, match="not grouped"):
        paged_attention(jnp.zeros((1, 3, 1, 8)), layer, t, p, None,
                        interpret=True)


# ---------------------------------------------------------------------------
# the routing knob + model-surface bit-identity
# ---------------------------------------------------------------------------


def test_paged_attn_impl_env_knob(monkeypatch):
    import jax

    on_tpu = jax.default_backend() == "tpu"
    monkeypatch.setenv("DSML_PAGED_ATTN", "pallas")
    assert paged_attn_impl() == "pallas"
    monkeypatch.setenv("DSML_PAGED_ATTN", "  XLA ")
    assert paged_attn_impl() == "xla"
    # unset/malformed: pallas on TPU, the gather elsewhere
    monkeypatch.delenv("DSML_PAGED_ATTN")
    assert paged_attn_impl() == ("pallas" if on_tpu else "xla")
    monkeypatch.setenv("DSML_PAGED_ATTN", "cuda")
    assert paged_attn_impl() == ("pallas" if on_tpu else "xla")


@pytest.mark.parametrize("quant", ["int4", "int8", False])
def test_decode_step_slots_paged_greedy_bit_identity(setup, monkeypatch,
                                                     quant):
    """The full decode surface: prefill a prompt into scattered pages,
    then run ``decode_step_slots_paged`` under all THREE implementations
    — the XLA gather, the single-buffer kernel, and the double-buffered
    kernel — greedy argmax tokens BIT-identical (the acceptance bar),
    logits within f32 reassociation noise, and the two kernel schedules
    bit-identical to each other (same ``_fold_page`` float sequence).
    Both entry points run jitted, as the batcher runs them (eager, the
    interpreter re-walks the kernel grid op by op on every call)."""
    cfg, model, params = setup
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, 21).astype(np.int32)
    page, c = 8, 8
    n_pt = cfg.max_seq // page
    pages = [5, 2, 9, 11]  # scattered; 4th page for decode growth
    table = np.zeros((1, n_pt), np.int32)
    table[0, : len(pages)] = pages

    def run(impl, pipe="0"):
        # the env knobs are read at trace time: one jit pair per implementation
        monkeypatch.setenv("DSML_PAGED_ATTN", impl)
        monkeypatch.setenv("DSML_PAGED_ATTN_PIPELINE", pipe)
        prefill = jax.jit(functools.partial(model.prefill_chunk_paged, quant=quant))
        decode = jax.jit(functools.partial(model.decode_step_slots_paged, quant=quant))
        pool = model.init_page_pool(14, page, quant=quant)
        for start in range(0, len(prompt), c):
            end = min(start + c, len(prompt))
            padded = np.zeros((1, c), np.int32)
            padded[0, : end - start] = prompt[start:end]
            last = (len(prompt) - 1) - start if end >= len(prompt) else c - 1
            logits, pool = prefill(
                params, pool, jnp.asarray(table), jnp.asarray(padded),
                jnp.int32(start), last_index=jnp.int32(last),
            )
        toks, rows = [], []
        tok = jnp.argmax(logits[0]).astype(jnp.int32)
        pos = len(prompt)
        for _ in range(5):
            toks.append(int(tok))
            logits, pool = decode(
                params, pool, jnp.asarray(table), tok[None],
                jnp.asarray([pos], jnp.int32),
            )
            rows.append(np.asarray(logits[0]))
            tok = jnp.argmax(logits[0]).astype(jnp.int32)
            pos += 1
        return toks, rows

    toks_x, rows_x = run("xla")
    toks_p, rows_p = run("pallas", pipe="0")
    toks_d, rows_d = run("pallas", pipe="1")
    assert toks_x == toks_p == toks_d
    for rx, rp in zip(rows_x, rows_p):
        np.testing.assert_allclose(rx, rp, atol=1e-4, rtol=1e-4)
    # the double-buffered kernel is not merely close to the single-buffer
    # kernel: identical float sequence, identical bits
    for rp, rd in zip(rows_p, rows_d):
        assert np.array_equal(rp, rd)


def test_llama_gqa_paged_batcher_pallas_parity(monkeypatch):
    """End-to-end GQA: the Llama paged batcher (n_kv_head=2 < n_head=8)
    emits identical greedy tokens under the kernel and the gather."""
    from dsml_tpu.serving import ContinuousBatcher

    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    params = model.init(0)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
               for l in (6, 19)]

    def drain(impl):
        monkeypatch.setenv("DSML_PAGED_ATTN", impl)
        b = ContinuousBatcher(model, params, n_slots=2, prefill_chunk=8,
                              paged_kv="int4", page_size=8, n_pages=30)
        rids = [b.submit(p, 4) for p in prompts]
        out = b.run()
        return [out[r] for r in rids]

    assert drain("xla") == drain("pallas")


# ---------------------------------------------------------------------------
# analytic HBM accounting: live-shaped vs table-shaped
# ---------------------------------------------------------------------------


def test_paged_hbm_bytes_scales_with_live_pages():
    """The kernel's bill is LIVE-shaped (linear in live pages, pool size
    absent); the gather's is TABLE-shaped (constant in live pages, ~pool
    table size): exact linearity, and a quarter-live pool reads > 4x less."""
    kw = dict(n_slots=8, n_pt=16, page_size=16, n_kv_head=8, head_dim=64,
              mode="int4")
    p25 = paged_hbm_bytes(live_pages=32, impl="pallas", **kw)
    p50 = paged_hbm_bytes(live_pages=64, impl="pallas", **kw)
    p75 = paged_hbm_bytes(live_pages=96, impl="pallas", **kw)
    p100 = paged_hbm_bytes(live_pages=128, impl="pallas", **kw)
    x25 = paged_hbm_bytes(live_pages=32, impl="xla", **kw)
    x100 = paged_hbm_bytes(live_pages=128, impl="xla", **kw)
    # pallas: linear in live table entries (the per-slot scratch fetches
    # and q/o bytes are the only — constant — offsets)
    assert p50 - p25 == p75 - p50 == p100 - p75 > 0
    # xla: the gather bill never moves with live pages
    assert x25 == x100
    # at a sparse pool the kernel touches far less HBM than the gather
    assert p25 * 5 < x25
    # both count the same query/output traffic (honesty: subtracting it
    # leaves pure pool traffic, and the pallas pool bill at FULL live
    # occupancy is still below the gather's read+materialize+reread)
    assert p100 < x100
    with pytest.raises(ValueError, match="unknown paged-attention impl"):
        paged_hbm_bytes(live_pages=1, impl="cuda", **kw)


def test_paged_hbm_bytes_codec_rows(setup):
    """Per-page bytes ride ``kv_row_bytes``: int4 pages cost ~7× less
    than fp pages at hd=64, and the dense-view write-back doubles the
    gather bill's materialization term."""
    from dsml_tpu.ops.quantization import kv_row_bytes

    kw = dict(n_slots=1, n_pt=4, page_size=16, n_kv_head=8, head_dim=64,
              live_pages=4)
    for mode in (None, "int8", "int4"):
        one_page = 8 * 16 * 2 * kv_row_bytes(64, mode)
        got = paged_hbm_bytes(mode=mode, impl="pallas", **kw)
        qo = 2 * 1 * 8 * 1 * 64 * 4
        # 4 live entries + the one slot's scratch-tail fetch
        assert got == (4 + 1) * one_page + qo


def test_paged_row_bytes_pins_scale_traffic():
    """``_paged_row_bytes``'s (payload, scale) split must sum to the
    codec's ``kv_row_bytes`` for K+V — a bill that dropped the per-row
    f32 scale columns would understate int4 traffic by 8 bytes per
    position (20% at hd=64)."""
    from dsml_tpu.ops.paged_attention import _paged_row_bytes
    from dsml_tpu.ops.quantization import kv_row_bytes

    for hd in (8, 64, 128):
        for mode, scale in ((None, 0), ("int8", 8), ("int4", 8)):
            payload, scales = _paged_row_bytes(hd, mode)
            assert scales == scale
            assert payload + scales == 2 * kv_row_bytes(hd, mode)
    # the concrete int4 figure the docstring quotes: payload alone at
    # hd=64 is 32+32 nibbled bytes, scales add 8 -> exactly +20% on 40
    payload, scales = _paged_row_bytes(64, "int4")
    assert (payload, scales) == (64, 8)


def test_paged_hbm_bytes_gqa_query_heads():
    """GQA: the q/o term is per QUERY head — a Llama pool with 2 kv heads
    serving 8 query heads moves 4x the query/output bytes of the rep=1
    default; the pool traffic term must not move at all."""
    kw = dict(n_slots=2, n_pt=4, page_size=8, n_kv_head=2, head_dim=64,
              mode="int4", live_pages=6, impl="pallas")
    base = paged_hbm_bytes(**kw)  # n_query_heads defaults to n_kv_head
    gqa = paged_hbm_bytes(n_query_heads=8, **kw)
    qo1 = 2 * 2 * 2 * 1 * 64 * 4
    assert gqa - base == 3 * qo1  # 8 query heads vs 2: +3 extra qo bills


# ---------------------------------------------------------------------------
# the double-buffered kernel: knob, bit-identity, VMEM guard
# ---------------------------------------------------------------------------


def test_paged_pipeline_env_knob(monkeypatch):
    import jax

    from dsml_tpu.ops.paged_attention import paged_pipeline

    monkeypatch.setenv("DSML_PAGED_ATTN_PIPELINE", "1")
    assert paged_pipeline() is True
    monkeypatch.setenv("DSML_PAGED_ATTN_PIPELINE", "off")
    assert paged_pipeline() is False
    # unset/auto/malformed: the single-buffer kernel on every backend —
    # Mosaic refuses the pipelined one (tests/test_tpu_compile.py), so
    # only an explicit "1" selects it
    monkeypatch.delenv("DSML_PAGED_ATTN_PIPELINE")
    assert paged_pipeline() is False
    monkeypatch.setenv("DSML_PAGED_ATTN_PIPELINE", "auto")
    assert paged_pipeline() is False


@pytest.mark.parametrize("mode", [None, "int8", "int4"])
def test_pipelined_kernel_bit_identical_all_codecs(mode):
    """The double-buffered slot-ring kernel vs the single-buffer grid
    walk, same scattered table with dead tails: outputs BIT-identical
    (np.array_equal, not allclose) — both delegate every fold to
    ``_fold_page``, so the schedules may differ but the floats may not."""
    rng = np.random.default_rng(7)
    layer, k, v = _make_pool_layer(rng, 12, 2, 8, 8, mode)
    table = np.zeros((3, 4), np.int32)
    table[0, :3] = [7, 2, 10]
    table[1, :1] = [5]
    table[2, :1] = [9]
    positions = np.asarray([[20], [7], [0]], np.int32)
    q = rng.standard_normal((3, 2, 1, 8)).astype(np.float32)

    single = np.asarray(paged_attention(
        jnp.asarray(q), layer, jnp.asarray(table), jnp.asarray(positions),
        mode, interpret=True, pipeline=False,
    ))
    double = np.asarray(paged_attention(
        jnp.asarray(q), layer, jnp.asarray(table), jnp.asarray(positions),
        mode, interpret=True, pipeline=True,
    ))
    assert np.array_equal(single, double)
    want = _oracle(q, k, v, table, positions, 8)
    np.testing.assert_allclose(double, want, atol=2e-5, rtol=2e-5)


def test_pipelined_kernel_verify_window_gqa():
    """Pipeline × the other grid shapes in one go: GQA grouping (rep=4)
    and the C>1 verify window stay bit-identical across schedules."""
    rng = np.random.default_rng(8)
    layer, k, v = _make_pool_layer(rng, 10, 2, 8, 8, "int4")
    table = np.zeros((2, 4), np.int32)
    table[0, :2] = [3, 8]
    table[1, :3] = [6, 1, 4]
    start = np.asarray([9, 17], np.int32)
    positions = start[:, None] + np.arange(3)[None, :]
    q = rng.standard_normal((2, 8, 3, 8)).astype(np.float32)
    runs = [
        np.asarray(paged_attention(
            jnp.asarray(q), layer, jnp.asarray(table),
            jnp.asarray(positions), "int4", interpret=True, pipeline=pipe,
        ))
        for pipe in (False, True)
    ]
    assert np.array_equal(runs[0], runs[1])
    np.testing.assert_allclose(
        runs[1], _oracle(q, k, v, table, positions.astype(np.int32), 8),
        atol=2e-5, rtol=2e-5)


def test_vmem_guard_raises_not_falls_back(monkeypatch):
    """Starve the VMEM budget: the router refuses a geometry-aware pallas
    request and a direct ``pipeline=True`` call refuses its slot ring —
    both with a ValueError naming geometry, estimate and budget; neither
    gives way to another path."""
    from dsml_tpu.ops import vmem_budget
    from dsml_tpu.ops.paged_attention import paged_vmem_bytes

    rng = np.random.default_rng(9)
    layer, k, v = _make_pool_layer(rng, 6, 2, 8, 8, "int8")
    table = np.asarray([[3, 0]], np.int32)
    positions = np.asarray([[9]], np.int32)
    q = rng.standard_normal((1, 2, 1, 8)).astype(np.float32)

    # the env override floors at 1 MiB — too roomy for a tiny test
    # geometry — so starve the module default directly
    monkeypatch.delenv("DSML_VMEM_LIMIT_MB", raising=False)
    monkeypatch.setattr(vmem_budget, "_DEFAULT_VMEM_BYTES", 16 * 1024)
    need = paged_vmem_bytes(8, 8, "int8")
    assert not vmem_budget.fits_vmem(need)
    budget = int(16 * 1024 * 0.9)
    monkeypatch.setenv("DSML_PAGED_ATTN", "pallas")
    with pytest.raises(ValueError, match=rf"page_size=8, head_dim=8, "
                       rf"mode=int8.*{need} B.*{budget} B"):
        paged_attn_impl(page_size=8, head_dim=8, mode="int8")
    # geometry-less calls keep the env-only contract
    assert paged_attn_impl() == "pallas"
    with pytest.raises(ValueError, match=rf"slot ring.*{need} B.*{budget} B"):
        paged_attention(
            jnp.asarray(q), layer, jnp.asarray(table), jnp.asarray(positions),
            "int8", interpret=True, pipeline=True,
        )


def test_vmem_budget_sizing_rules(monkeypatch):
    """The budget arithmetic the guards share: Mosaic-padded block
    footprints, the env override, the spend fraction."""
    from dsml_tpu.ops import vmem_budget

    # lane padding: a (8, 1) f32 column costs a full 128-lane stripe
    assert vmem_budget.vmem_block_bytes((8, 1), 4) == 8 * 128 * 4
    # sublane padding by itemsize: f32 rows pad to 8, int8 rows to 32
    assert vmem_budget.vmem_block_bytes((3, 128), 4) == 8 * 128 * 4
    assert vmem_budget.vmem_block_bytes((3, 128), 1) == 32 * 128
    # leading dims multiply through unpadded
    assert vmem_budget.vmem_block_bytes((2, 8, 128), 4) == 2 * 8 * 128 * 4
    # 1-D shapes are one sublane row
    assert vmem_budget.vmem_block_bytes((64,), 4) == 8 * 128 * 4
    # env override, malformed values fall back, spend fraction applies
    monkeypatch.setenv("DSML_VMEM_LIMIT_MB", "2")
    assert vmem_budget.vmem_limit_bytes() == 2 * 1024 * 1024
    assert vmem_budget.fits_vmem(int(2 * 1024 * 1024 * 0.9))
    assert not vmem_budget.fits_vmem(int(2 * 1024 * 1024 * 0.9) + 1)
    monkeypatch.setenv("DSML_VMEM_LIMIT_MB", "zero")
    assert vmem_budget.vmem_limit_bytes() == 16 * 1024 * 1024
    monkeypatch.setenv("DSML_VMEM_LIMIT_MB", "-4")
    assert vmem_budget.vmem_limit_bytes() == 16 * 1024 * 1024
