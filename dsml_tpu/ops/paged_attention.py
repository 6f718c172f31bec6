"""Pallas paged-attention decode kernel — gather-free reads of the KV page
pool (the vLLM paged-attention kernel shape, PAPERS.md).

The XLA paged path (``GPT2._paged_attn_inputs``) gathers ``pool[page_table]``
into a dense ``[b, H, max_seq, hd]`` view per layer per tick. On real chips
that round-trips the ENTIRE table width through HBM — gather read, dense
materialization write, attention read — every tick, which erases most of the
paged cache's bandwidth win (capacity still holds; traffic doesn't). This
kernel walks the page table directly instead:

- **One page per grid step.** The table rides as a SCALAR-PREFETCH operand
  (``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index maps read
  ``table[b, t]`` and Pallas DMAs exactly that physical page's rows into
  VMEM for grid step ``(b, kv_head, t)`` — the dense view is never
  materialized, and HBM traffic is proportional to the pages the table
  actually names (:func:`paged_hbm_bytes` is the analytic accounting).
- **In-kernel dequantize.** int4 pages unpack their nibbles (the shared
  ``pack_int4`` layout: channel halves contiguous) and both int4/int8 fold
  the per-row scales from ``quantize_kv_rows`` exactly where the XLA path
  does — key scales after the q·k dot, value scales into the probabilities
  before the p·v dot — so the math is the same sum in a different order.
- **Running (out, lse) merge.** Pages fold into online-softmax accumulators
  (running row-max, running denominator — the same logsumexp-merge shape as
  ``ops.ring_attention``'s hop merge), held in VMEM scratch across the
  page-walk grid dimension.
- **Dead-page skipping.** The batcher's sanitized table points every entry
  past a slot's live depth (and every dead slot's entire row) at the
  scratch page 0; pages whose first row is beyond every resident query's
  position skip compute via ``pl.when``, and the repeated scratch-page
  block index collapses to one resident copy — live work, not pool size,
  sets the bill.
- **GQA for free.** Query heads group over their kv head exactly like
  ``Llama._decode_attention``: the grid walks KV heads and each step's q
  block is that head's query GROUP (``rep × C`` rows), so one kernel serves
  GPT-2 (rep=1) and Llama (rep>1), dense-parity pinned for both.

Routing: ``DSML_PAGED_ATTN=pallas|xla`` (:func:`paged_attn_impl`; default
pallas on TPU, xla elsewhere — the gather path stays the parity oracle).
All three paged serving surfaces (decode / chunked prefill / speculative
verify) route through here via ``_decode_core_paged``: their masks are all
``key_pos <= query_pos``, which is the one mask this kernel implements. On non-TPU backends the kernel runs under the Pallas
interpreter, which is how CI pins parity on the CPU mesh.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

try:  # pltpu imports on CPU builds too; guard anyway (ops/flash.py idiom)
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from dsml_tpu.ops.vmem_budget import require_vmem, vmem_block_bytes

__all__ = [
    "paged_attention",
    "paged_attn_impl",
    "paged_pipeline",
    "paged_vmem_bytes",
    "paged_hbm_bytes",
]

_NEG_INF = -1e30
_MAX_FLOOR = -1e20  # running-max floor: exp() stays sane on fully-masked rows


def paged_attn_impl(
    page_size: int | None = None,
    head_dim: int | None = None,
    mode: str | None = None,
    n_query_rows: int = 8,
) -> str:
    """The paged-attention routing knob: ``DSML_PAGED_ATTN`` ∈
    {"pallas", "xla"}; unset/malformed defaults to the Pallas kernel on
    TPU and the XLA gather elsewhere (the kernel still RUNS off-TPU via
    the interpreter — tests opt in explicitly — but interpreted ticks are
    the wrong default for a CPU serving loop). Read at trace time: a
    batcher compiles its programs once, so flip the env before
    construction, not between ticks.

    When the caller passes its page GEOMETRY a ``pallas`` answer is
    additionally checked against the VMEM budget: a page whose kernel
    working set can't fit the chip's VMEM would die inside Mosaic with an
    opaque allocation error at compile time, so this raises a
    ``ValueError`` naming the geometry, the estimate and the budget
    instead. Geometry-less calls keep the env-only behavior (the knob
    test's contract)."""
    raw = os.environ.get("DSML_PAGED_ATTN", "").strip().lower()
    if raw not in ("pallas", "xla"):
        raw = "pallas" if jax.default_backend() == "tpu" else "xla"
    if raw == "pallas" and page_size is not None and head_dim is not None:
        require_vmem(
            paged_vmem_bytes(page_size, head_dim, mode,
                             n_query_rows=n_query_rows,
                             pipeline=paged_pipeline()),
            f"paged-attention kernel at page_size={page_size}, "
            f"head_dim={head_dim}, mode={mode}",
        )
    return raw


def paged_pipeline() -> bool:
    """The double-buffer knob: ``DSML_PAGED_ATTN_PIPELINE`` ∈ {"1"/"on",
    "0"/"off"}; unset/"auto"/malformed selects the single-buffer kernel
    on every backend. Mosaic refuses the hand-pipelined kernel at every
    geometry with head_dim < 128 (``tests/test_tpu_compile.py`` carries
    the message), so it is never chosen on its own; an explicit "1" still
    selects it and lets the compiler's error surface. Read at trace time,
    like ``DSML_PAGED_ATTN``."""
    raw = os.environ.get("DSML_PAGED_ATTN_PIPELINE", "").strip().lower()
    return raw in ("1", "on", "true")


def paged_vmem_bytes(
    page_size: int,
    head_dim: int,
    mode: str | None,
    n_query_rows: int = 8,
    pipeline: bool = True,
) -> int:
    """Analytic VMEM working set of one paged-attention grid step, at the
    Mosaic-padded footprint of every buffer (``vmem_budget`` sizing rule).
    Both kernels stream pages 2-deep — the pipelined kernel through its
    explicit scratch slots, the single-buffer kernel through Pallas'
    automatic BlockSpec double buffering — so the page term doubles either
    way; the pipelined kernel additionally keeps its own DMA slots for the
    scale columns, and both carry the q/out blocks plus the (acc, m, l)
    online-softmax scratch."""
    wk = head_dim // 2 if mode == "int4" else head_dim
    item = 1 if mode else 4
    depth = 2  # 2-deep streaming either way (manual slots / auto pipeline)
    page = depth * 2 * vmem_block_bytes((page_size, wk), item)
    scales = depth * 2 * vmem_block_bytes((page_size, 1), 4) if mode else 0
    qo = 2 * vmem_block_bytes((n_query_rows, head_dim), 4)
    acc = vmem_block_bytes((n_query_rows, head_dim), 4)
    ml = 2 * vmem_block_bytes((n_query_rows, 128), 4)
    pos = vmem_block_bytes((8, n_query_rows), 4)
    return page + scales + qo + acc + ml + pos


def _vmem_spec(block_shape, index_map):
    if pltpu is not None:
        return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)
    return pl.BlockSpec(block_shape, index_map)  # pragma: no cover


def _scratch(shape):
    if pltpu is not None:
        return pltpu.VMEM(shape, jnp.float32)
    return pl.MemoryRef(shape, jnp.float32)  # pragma: no cover


def _kernel(table_ref, q_ref, pos_ref, k_ref, v_ref, *rest, mode, scale,
            page_size, n_pt, g_rows):
    """One (batch row, kv head, table entry) grid step: DMA'd page →
    dequantize → masked scores → online-softmax fold into the running
    (acc, m, l) scratch. ``rest`` is ``(k_s_ref, v_s_ref, o_ref, acc, m,
    l)`` for quantized pools and ``(o_ref, acc, m, l)`` for fp pages."""
    if mode:
        k_s_ref, v_s_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        k_s_ref, v_s_ref = None, None
        o_ref, acc, m_scr, l_scr = rest
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _MAX_FLOOR)
        l_scr[:] = jnp.zeros_like(l_scr)

    posq = pos_ref[0, 0].reshape(g_rows, 1)  # [G, 1] global query positions
    # pages whose FIRST row is past every resident query are fully masked
    # for this batch row — skip the compute (the sanitized table routes
    # them at the scratch page, whose repeated block index Pallas fetches
    # once; the skip is what keeps the MXU bill proportional to live rows)
    max_pos = jnp.max(posq)

    @pl.when(t * page_size <= max_pos)
    def _compute():
        # dequant → key scales AFTER the q·k dot, value scales into the
        # probabilities BEFORE the p·v dot — identical math to the XLA
        # path's scores * k_s^T / probs * v_s^T, shared verbatim with the
        # double-buffered kernel via _fold_page
        _fold_page(
            q_ref, posq, k_ref[0, 0], v_ref[0, 0],
            k_s_ref[0, 0] if mode else None,
            v_s_ref[0, 0] if mode else None,
            acc, m_scr, l_scr,
            mode=mode, scale=scale, page_size=page_size, g_rows=g_rows, t=t,
        )

    @pl.when(t == n_pt - 1)
    def _finish():
        l_fin = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc[:] / l_fin).astype(o_ref.dtype)


def _fold_page(q_ref, posq, k_page, v_page, ks_page, vs_page, acc, m_scr,
               l_scr, *, mode, scale, page_size, g_rows, t):
    """Fold ONE resident page into the online-softmax accumulators — the
    exact float sequence of :func:`_kernel`'s ``_compute`` body (dequant →
    masked scores → running-max merge), factored out so the single-buffer
    and double-buffered kernels share it: bit-identical outputs are an
    acceptance criterion, and sharing the math is how it stays pinned."""
    q = q_ref[0, 0].astype(jnp.float32)  # [G, hd]
    if mode == "int4":
        hi = (k_page >> 4).astype(jnp.int8) - 8
        lo = (k_page & 0xF).astype(jnp.int8) - 8
        k = jnp.concatenate([hi, lo], axis=-1).astype(jnp.float32)
    else:
        k = k_page.astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, page]
    if mode:
        s = s * ks_page.reshape(1, page_size)
    k_pos = t * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (g_rows, page_size), 1
    )
    s = jnp.where(k_pos <= posq, s, _NEG_INF)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:] = jnp.broadcast_to(
        l_scr[:, :1] * corr + jnp.sum(p, -1, keepdims=True), l_scr.shape
    )
    if mode == "int4":
        hi = (v_page >> 4).astype(jnp.int8) - 8
        lo = (v_page & 0xF).astype(jnp.int8) - 8
        v = jnp.concatenate([hi, lo], axis=-1).astype(jnp.float32)
    else:
        v = v_page.astype(jnp.float32)
    if mode:
        p = p * vs_page.reshape(1, page_size)
    acc[:] = acc[:] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)


def _pipelined_kernel(table_ref, q_ref, pos_ref, k_hbm, v_hbm, *rest, mode,
                      scale, page_size, n_pt, g_rows):
    """The hand-pipelined page walk: grid is (batch row, kv head) and the
    kernel itself streams that row's LIVE table entries through a 2-deep
    VMEM slot ring — while entry ``t`` computes, entry ``t+1``'s page DMA
    is already in flight (``pltpu.make_async_copy``), so the MXU never
    waits a full page-fetch latency between entries. The pool stays in
    HBM (``ANY`` memory space); only the walked pages ever reach VMEM.

    Dead/scratch entries never enter the pipeline at all: the loop bound
    is the row's live depth (``max_pos // page_size + 1``, straight from
    the resident positions), so a slot's dead-entry tail costs neither
    DMA nor a predicated bubble — the skip CANNOT stall the pipeline
    because skipped entries are never issued. A fully dead slot
    (all positions −1) runs zero iterations and emits zeros, exactly
    like the single-buffer kernel's all-skipped walk."""
    if mode:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf,
         acc, m_scr, l_scr, sem) = rest
    else:
        o_ref, k_buf, v_buf, acc, m_scr, l_scr, sem = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    bi = pl.program_id(0)
    hi = pl.program_id(1)

    posq = pos_ref[0, 0].reshape(g_rows, 1)  # [G, 1] global query positions
    max_pos = jnp.max(posq)
    # live table entries for this batch row: positions 0..max_pos span
    # pages 0..max_pos // page_size (max_pos == -1 ⇒ zero live entries)
    n_live = jnp.minimum((max_pos + page_size) // page_size, n_pt)

    def _copies(slot, t):
        page = table_ref[bi, t]
        cps = [
            pltpu.make_async_copy(k_hbm.at[page, hi], k_buf.at[slot],
                                  sem.at[slot, 0]),
            pltpu.make_async_copy(v_hbm.at[page, hi], v_buf.at[slot],
                                  sem.at[slot, 1]),
        ]
        if mode:
            cps.append(pltpu.make_async_copy(ks_hbm.at[page, hi],
                                             ks_buf.at[slot], sem.at[slot, 2]))
            cps.append(pltpu.make_async_copy(vs_hbm.at[page, hi],
                                             vs_buf.at[slot], sem.at[slot, 3]))
        return cps

    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, _MAX_FLOOR)
    l_scr[:] = jnp.zeros_like(l_scr)

    @pl.when(n_live > 0)
    def _prologue():  # warm-up: slot 0's DMA issues before any compute
        for c in _copies(0, 0):
            c.start()

    def _body(t, carry):
        slot = lax.rem(t, 2)

        @pl.when(t + 1 < n_live)
        def _prefetch_next():  # next entry's DMA flies while t computes
            for c in _copies(lax.rem(t + 1, 2), t + 1):
                c.start()

        for c in _copies(slot, t):
            c.wait()
        _fold_page(
            q_ref, posq, k_buf[slot], v_buf[slot],
            ks_buf[slot] if mode else None,
            vs_buf[slot] if mode else None,
            acc, m_scr, l_scr,
            mode=mode, scale=scale, page_size=page_size, g_rows=g_rows, t=t,
        )
        return carry

    lax.fori_loop(0, n_live, _body, 0)

    l_fin = jnp.maximum(l_scr[:, :1], 1e-30)
    o_ref[0, 0] = (acc[:] / l_fin).astype(o_ref.dtype)


def _any_spec():
    return pl.BlockSpec(memory_space=pl.ANY)


def paged_attention(
    q: jax.Array,
    pool_layer: dict,
    page_table: jax.Array,
    positions: jax.Array,
    mode: str | None,
    interpret: bool | None = None,
    pipeline: bool | None = None,
) -> jax.Array:
    """Decode attention straight off the page pool — no dense
    ``[b, H, S, hd]`` view.

    ``q`` [b, hq, C, hd] (C = 1 for decode, the window/chunk width for
    verify/prefill); ``pool_layer`` is ONE layer's pool entry dict
    (``k``/``v`` [P, hkv, page_size, ·] plus ``k_s``/``v_s`` [P, hkv,
    page_size, 1] when quantized — ``init_page_pool``'s layout);
    ``page_table`` [b, n_pt] int32 physical page per (slot, logical page)
    — the batcher's SANITIZED table (dead slots/entries at scratch page
    0); ``positions`` [b, C] int32 global positions of the query rows.
    The mask is ``key_pos <= query_pos`` — exactly the ``valid`` mask all
    three paged serving surfaces pass the XLA path. ``mode`` ∈ {None,
    "int8", "int4"} is the pool codec. Returns [b, hq, C, hd] in
    ``q.dtype``; numeric parity with the gather path and greedy-token
    bit-identity through the paged batcher are pinned in tests.

    ``pipeline`` selects the kernel: ``True`` streams pages through the
    hand-pipelined 2-deep DMA slot ring (:func:`_pipelined_kernel` —
    entry ``t+1``'s fetch overlaps entry ``t``'s math), ``False`` the
    single-buffer grid walk, ``None`` defers to
    ``DSML_PAGED_ATTN_PIPELINE`` (:func:`paged_pipeline`). Both kernels
    fold pages through the SAME ``_fold_page`` float sequence over the
    SAME live-entry order, so outputs are bit-identical — the
    single-buffer kernel is the pipelined kernel's parity oracle. A slot
    ring that can't fit VMEM raises a ``ValueError``
    (:mod:`dsml_tpu.ops.vmem_budget`)."""
    if mode not in (None, "int8", "int4"):
        raise ValueError(f"unknown page quant mode {mode!r}")
    b, hq, c, hd = q.shape
    n_pages, hkv, page_size, _ = pool_layer["k"].shape
    if hq % hkv:
        raise ValueError(f"query heads {hq} not grouped by kv heads {hkv}")
    n_pt = page_table.shape[1]
    rep = hq // hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if pipeline is None:
        pipeline = paged_pipeline()
    if pipeline:
        require_vmem(
            paged_vmem_bytes(page_size, hd, mode, pipeline=True),
            f"double-buffered paged-attention slot ring at "
            f"page_size={page_size}, head_dim={hd}, mode={mode}",
        )

    # group query heads over their kv head (the GQA grouping rule — head
    # h serves kv head h // rep, matching Llama._decode_attention), then
    # flatten (rep, C) into one query-row axis: all of a kv head's queries
    # share its pages, so one grid step scores the whole group
    qg = q.reshape(b, hkv, rep, c, hd).reshape(b, hkv, rep * c, hd)
    posq = jnp.broadcast_to(
        jnp.asarray(positions, jnp.int32)[:, None, :], (b, rep, c)
    ).reshape(b, rep * c)
    g = rep * c
    gp = max(8, -(-g // 8) * 8)  # sublane-tileable query-row count
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
        # padded rows mask everything (-1 admits no key position); their
        # zero q rows produce finite garbage that is sliced off below
        posq = jnp.pad(posq, ((0, 0), (0, gp - g)), constant_values=-1)
    # positions ride VMEM broadcast over 8 sublanes (the flash lse trick:
    # the block shape stays Mosaic-tileable)
    pos8 = jnp.broadcast_to(posq[:, None, :], (b, 8, gp))

    if pltpu is None:  # pragma: no cover — pltpu importable on all builds
        raise RuntimeError("pallas TPU frontend unavailable")

    if pipeline:
        # grid walks (batch row, kv head); the kernel streams that row's
        # live table entries itself through the 2-deep DMA slot ring —
        # the pool operands stay in HBM (ANY), only walked pages land in
        # the VMEM scratch slots
        kernel = functools.partial(
            _pipelined_kernel, mode=mode, scale=hd ** -0.5,
            page_size=page_size, n_pt=n_pt, g_rows=gp,
        )
        in_specs = [
            _vmem_spec((1, 1, gp, hd), lambda bi, hi, tab: (bi, hi, 0, 0)),
            _vmem_spec((1, 8, gp), lambda bi, hi, tab: (bi, 0, 0)),
            _any_spec(), _any_spec(),
        ]
        operands = [qg, pos8, pool_layer["k"], pool_layer["v"]]
        kdt = pool_layer["k"].dtype
        scratch = [
            pltpu.VMEM((2, page_size, pool_layer["k"].shape[-1]), kdt),
            pltpu.VMEM((2, page_size, pool_layer["v"].shape[-1]), kdt),
        ]
        if mode:
            in_specs += [_any_spec(), _any_spec()]
            operands += [pool_layer["k_s"], pool_layer["v_s"]]
            scratch += [
                pltpu.VMEM((2, page_size, 1), jnp.float32),
                pltpu.VMEM((2, page_size, 1), jnp.float32),
            ]
        scratch += [
            _scratch((gp, hd)), _scratch((gp, 128)), _scratch((gp, 128)),
            pltpu.SemaphoreType.DMA((2, 4 if mode else 2)),
        ]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv),
            in_specs=in_specs,
            out_specs=_vmem_spec((1, 1, gp, hd),
                                 lambda bi, hi, tab: (bi, hi, 0, 0)),
            scratch_shapes=scratch,
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, hkv, gp, hd), jnp.float32),
            interpret=interpret,
        )(jnp.asarray(page_table, jnp.int32), *operands)
        out = out[:, :, :g].reshape(b, hkv, rep, c, hd).reshape(b, hq, c, hd)
        return out.astype(q.dtype)

    kernel = functools.partial(
        _kernel, mode=mode, scale=hd ** -0.5, page_size=page_size,
        n_pt=n_pt, g_rows=gp,
    )
    in_specs = [
        _vmem_spec((1, 1, gp, hd), lambda bi, hi, ti, tab: (bi, hi, 0, 0)),
        _vmem_spec((1, 8, gp), lambda bi, hi, ti, tab: (bi, 0, 0)),
        # the page walk: table[b, t] names the physical page this grid
        # step reads — Pallas DMAs that page's rows, nothing else
        _vmem_spec((1, 1, page_size, pool_layer["k"].shape[-1]),
                   lambda bi, hi, ti, tab: (tab[bi, ti], hi, 0, 0)),
        _vmem_spec((1, 1, page_size, pool_layer["v"].shape[-1]),
                   lambda bi, hi, ti, tab: (tab[bi, ti], hi, 0, 0)),
    ]
    operands = [qg, pos8, pool_layer["k"], pool_layer["v"]]
    if mode:
        in_specs += [
            _vmem_spec((1, 1, page_size, 1),
                       lambda bi, hi, ti, tab: (tab[bi, ti], hi, 0, 0)),
            _vmem_spec((1, 1, page_size, 1),
                       lambda bi, hi, ti, tab: (tab[bi, ti], hi, 0, 0)),
        ]
        operands += [pool_layer["k_s"], pool_layer["v_s"]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, n_pt),
        in_specs=in_specs,
        out_specs=_vmem_spec((1, 1, gp, hd),
                             lambda bi, hi, ti, tab: (bi, hi, 0, 0)),
        scratch_shapes=[
            _scratch((gp, hd)), _scratch((gp, 128)), _scratch((gp, 128)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, hd), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(page_table, jnp.int32), *operands)
    out = out[:, :, :g].reshape(b, hkv, rep, c, hd).reshape(b, hq, c, hd)
    return out.astype(q.dtype)


def paged_hbm_bytes(
    n_slots: int,
    n_pt: int,
    page_size: int,
    n_kv_head: int,
    head_dim: int,
    mode: str | None,
    live_pages: int,
    impl: str,
    n_query_rows: int = 1,
    n_query_heads: int | None = None,
) -> int:
    """Analytic HBM bytes ONE layer's paged-attention read costs per
    decode tick — counted from the program structure, not sampled (the
    ``collectives.ring_wire_bytes`` contract), with the scratch-page
    term charged at its worst case.
    ``tests/test_paged_attention.py::test_paged_hbm_bytes_scales_with_live_pages``
    reads this.

    Every quantized page moves its PAYLOAD and its SCALES: the kernel
    DMAs the per-row f32 scale columns (``k_s``/``v_s``, 4 bytes per K
    row and per V row) alongside the packed payload, and the gather path
    gathers them, so both bills carry an explicit per-row scale term —
    8 bytes per position under int8/int4, zero for fp pages. The split
    (``_paged_row_bytes``) is pinned against ``kv_row_bytes`` in
    ``test_paged_attention.py``; a model that counted packed payload
    alone would understate int4 traffic by 20% at head_dim 64.

    ``impl="xla"`` — the gather path's bill is TABLE-shaped: it reads one
    page per table entry for every slot (``n_slots × n_pt`` pages, the
    scratch page re-read per duplicate entry), writes the gathered dense
    view, and reads that view back in the attention dots — regardless of
    how many rows are live. ``impl="pallas"`` — the kernel's bill is
    LIVE-shaped: ``live_pages`` counts live TABLE ENTRIES summed over
    slots (a CoW-shared page counts once per slot naming it — each
    (slot, head) grid row DMAs its own copy), each entry fetches once
    per kv head, and each slot's dead-entry tail re-fetches the scratch
    page once per (slot, head) run — the ``+ n_slots`` term (a slot with
    zero dead entries skips it; this model charges the worst case; the
    double-buffered kernel never fetches the tail at all, so its bill
    is bounded above by this). Query/output bytes ride both and are
    counted for honesty — per QUERY head (``n_query_heads``, defaulting
    to ``n_kv_head`` for the rep=1 families; GQA callers pass their
    ``rep × n_kv_head``); they are noise next to the pool traffic."""
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown paged-attention impl {impl!r}")
    payload_row, scale_row = _paged_row_bytes(head_dim, mode)
    row = payload_row + scale_row  # one position's K + V + both scales
    page_bytes = n_kv_head * page_size * row
    hq = n_kv_head if n_query_heads is None else n_query_heads
    qo_bytes = 2 * n_slots * hq * n_query_rows * head_dim * 4
    if impl == "pallas":
        return (live_pages + n_slots) * page_bytes + qo_bytes
    gathered = n_slots * n_pt * page_bytes  # pool read, table-shaped
    # dense view materialized in the unpacked int8 (or fp) row width plus
    # scales, written once and read back by the attention dots
    dense_row = 2 * (head_dim + 4) if mode else 2 * 4 * head_dim
    dense = n_slots * n_pt * page_size * n_kv_head * dense_row
    return gathered + 2 * dense + qo_bytes


def _paged_row_bytes(head_dim: int, mode: str | None) -> tuple[int, int]:
    """(payload, scale) HBM bytes one POSITION moves through a paged
    read — K row + V row, and their two f32 scales when quantized. The
    sum equals ``2 * kv_row_bytes(head_dim, mode)`` by construction
    (pinned in tests); the split exists so callers and tests can see the
    scale traffic explicitly instead of trusting it is in there."""
    from dsml_tpu.ops.quantization import kv_row_bytes

    scale_row = 8 if mode else 0  # one f32 scale per K row + one per V row
    payload_row = 2 * kv_row_bytes(head_dim, mode) - scale_row
    return payload_row, scale_row
