"""Continuous-batching serving — slot-based decode with in-flight admission.

The reference has no inference path at all (SURVEY.md §5; its client only
trains, ``client.go:516-659``); the framework's serving stack already does
static batched decode (``GPT2.generate``/``generate_spmd``). This module
adds the throughput layer a real serving deployment needs: requests arrive
at different times with different prompt/output lengths, and a static
batch would idle every slot until the LONGEST request finishes. Continuous
batching (the vLLM/Orca scheduling idea) retires each request the moment
it completes and admits a queued one into the freed slot — realized here
TPU-first:

- ONE jitted decode program for all slots (``model.decode_step_slots``):
  fully static shapes, per-slot depths carried as a ``pos`` vector, cache
  writes as a batched scatter, attention masked to ``s <= pos[b]`` per
  row. No recompilation ever happens at steady state.
- Prefill compiles once per PROMPT BUCKET (next power-of-two length):
  prompts are right-padded to the bucket, the logits read at the true
  last index (``prefill(last_index=L-1)``), and the new request's cache
  rows are scattered into its slot.
- The host-side scheduler is a plain loop: admit → decode → emit/retire.
  Sampling is greedy or temperature-based with a per-request key, so a
  request's tokens are independent of which slot/step served it.

Single-device by design (the TP/DP-sharded decode lives in
``generate_spmd``); slots × continuous admission is the axis this module
adds.

This module is also the DECODE WORKER of the disaggregated serving fleet
(``dsml_tpu.serving.router``): :meth:`ContinuousBatcher.inject` admits a
request whose prefill already ran on a PREFILL worker — the handed-off KV
rows scatter into a slot exactly like a local admission's, and the first
token samples from the handed-off logits with the identical
(seed, key_rid, step) PRNG fold, so disaggregation never changes tokens
(pinned in tests).

``paged_kv`` replaces the dense per-slot cache with a PAGED one: a pool
of fixed-size token pages (int4 block-quantized by default), a per-slot
page table the attention gathers through, and a host-side refcounting
allocator (``serving.paging``) — so a chip's HBM pays for the rows
requests actually hold instead of ``n_slots × max_seq`` dense rows, and
registered prefixes become COPY-ON-WRITE page-table entries shared
read-only across every matching request (docs/SERVING.md § Paged KV).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from dsml_tpu.obs import get_registry

__all__ = ["Request", "ContinuousBatcher", "QueueFull"]


class QueueFull(RuntimeError):
    """``submit`` rejected by the queue cap (``max_queue``): the batcher
    sheds load explicitly instead of letting an unbounded queue grow until
    every request's latency is unbounded too. Counted in
    ``serving_shed_total``; callers (routers, the ``DecodeFleet``) retry
    elsewhere or surface backpressure upstream."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [L] int32
    max_new_tokens: int
    tokens: list = dataclasses.field(default_factory=list)  # emitted so far
    done: bool = False
    # wall-clock marks for the serving latency metrics (time.monotonic)
    submitted_at: float = 0.0
    first_token_at: float | None = None
    finished_at: float | None = None
    last_emit_at: float | None = None
    # sampler identity override: the PRNG key folds (seed, key_rid, step)
    # instead of the LOCAL rid — how a fleet keeps sampled tokens identical
    # to a reference batcher whose rids differ from this replica's (the
    # router stamps its fleet-wide rid here; None = use ``rid``)
    key_rid: int | None = None
    # request-scoped trace identity (obs.TraceContext.trace_id): stamped
    # by the router at submit and carried through the handoff wire — the
    # decode-side spans/flow events and the admission-histogram exemplar
    # all tag with it, so a tail latency resolves to ONE request's trace
    trace_id: str | None = None
    # preemption priority (paged ``preemption=True`` only): under page
    # pressure the LOWEST-priority active slot is evicted first (ties
    # break youngest-first, so FIFO order degrades last). Pure
    # scheduling — tokens never depend on it.
    priority: int = 0

    def trace_ctx(self):
        """The request's TraceContext (flow id derives from trace_id
        alone, so the decode side rebuilds it without extra wire state);
        None when the request carries no trace."""
        if self.trace_id is None:
            return None
        from dsml_tpu.obs import TraceContext

        return TraceContext(trace_id=self.trace_id)


def _bucket(n: int, buckets: tuple) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds the largest bucket {buckets[-1]}")


# the host-side prompt-lookup draft rule lives with its device twin in
# models/speculative.py — ONE drafting rule for the standalone speculator
# and the batcher's speculative tick (equivalence pinned in tests)
from dsml_tpu.models.speculative import lookup_draft_host as _lookup_draft


class ContinuousBatcher:
    """Slot-based continuous-batching decoder over one model + params.

    ``submit`` enqueues prompts; ``step`` admits queued requests into free
    slots (bucketed prefill), runs one decode QUANTUM, emits new tokens,
    and retires finished requests (EOS or token budget). ``run`` drains
    everything. Greedy by default; ``temperature > 0`` samples with a
    per-(request, step) fold of ``seed`` so results don't depend on slot
    timing.

    ``decode_quantum`` — tokens decoded per scheduler tick, chained inside
    ONE jitted ``lax.scan`` (sampling included). 1 = retire/admit at every
    token (max lane utilization). Each tick costs one host↔device round
    trip, which on any small model dwarfs the step compute — a quantum of k amortizes that k× at the cost of up
    to k−1 wasted lane-ticks when a request finishes mid-quantum
    (iteration-level vs token-level scheduling, the Orca trade-off).
    Tokens are IDENTICAL for any quantum; only throughput changes.

    ``prefill_chunk`` — when > 0, admission prefills prompts in chunks of
    that many tokens via ``model.prefill_chunk``, running at most one
    chunk per scheduler tick once a long admission is in flight: decode
    quanta continue BETWEEN a long prompt's chunks instead of every
    active slot stalling for the whole prefill (the head-of-line problem
    of whole-prompt admission; Orca/vLLM chunked prefill). Tokens are
    identical either way (chunk chaining == whole-prompt prefill — pinned
    in tests; with ``kv_quant`` the chunk path reads int8 cache rows for
    within-prompt attention, the standard chunked-prefill approximation).
    0 (default) keeps whole-prompt bucketed admission.

    ``register_prefix(tokens)`` — PREFIX CACHING for shared prompt heads
    (the system-prompt pattern): the prefix's cache rows and next-token
    logits are computed once; any later prompt starting with a registered
    prefix admits by COPYING those rows and chunk-prefilling only the
    suffix, cutting admission prefill from O(L) to O(L - P) (the TTFT
    win). Requires ``prefill_chunk > 0`` (the suffix rides the chunk
    path); the longest matching prefix is used; tokens are identical with
    or without the cache (prefix rows attend only within the prefix under
    causality, so they equal the full prefill's — pinned in tests).

    ``turbo_factor`` — when >= 2, a SECOND decode program with quantum
    ``decode_quantum * turbo_factor`` is compiled, and a scheduler tick
    escalates to it whenever the batcher is in steady-state decode: the
    queue is empty, no chunked admission is mid-flight, and at least one
    active request has the full turbo quantum's budget remaining (a slot
    that finishes mid-tick would have idled under plain ticks too — the
    queue is empty — so escalation wastes nothing a plain schedule would
    have used; an EOS or budget hit mid-turbo retires the slot and
    discards the tail, exactly as a plain quantum does). Dispatch cost
    drops ``turbo_factor``× in steady state while admission latency keeps
    the BASE quantum's granularity — the adaptive answer to the per-tick
    host RTT that a fixed large quantum would buy only by slowing every
    admission. Tokens are IDENTICAL with turbo on or off (the sampler
    folds (request, absolute step) — pinned in tests). A request submitted
    DURING a turbo tick waits out that tick (the trade-off vs the base
    quantum's admission cadence) — so keep the turbo quantum
    (``decode_quantum * turbo_factor`` tokens × the per-token step time)
    within the deployment's TTFT budget, or use ``adaptive_quantum``,
    whose early exit removes the trade-off entirely.

    ``adaptive_quantum`` — when >= 2, each decode tick runs an EARLY-EXIT
    device loop (``lax.while_loop``) of up to that many steps that stops
    the moment ANY active slot finishes (EOS or token budget). This
    dissolves the fixed-quantum trade-off: a tick never decodes past a
    retirement (zero wasted lane-ticks), a freed slot admits on the very
    next tick (zero admission delay beyond one tick boundary), and in
    steady state one host dispatch carries up to ``adaptive_quantum``
    tokens per slot. Dispatch count collapses from O(tokens/quantum) to
    ~O(retirements + admissions) — the fix for a high per-dispatch host
    cost that a fixed large quantum could only buy by delaying admissions
    and over-decoding retired slots. Works with greedy and temperature
    sampling; tokens are IDENTICAL to the plain
    batcher and to ``generate`` (same chain, sampler folds the absolute
    step — pinned in tests). While a chunked admission is mid-flight the
    scheduler drops back to plain ``decode_quantum`` ticks so prefill
    chunks keep interleaving with decode. Exclusive with ``turbo_factor``
    and ``speculative_window`` (each sets its own per-tick budget).

    ``speculative_window`` — when >= 2, each decode tick runs PROMPT-LOOKUP
    SPECULATIVE decoding across all slots: every active slot drafts
    window−1 tokens from the most recent n-gram match in its own history
    (host-side numpy — no device round trip), ONE ``model.verify_step``
    call scores every slot's window at its own depth, and each slot
    commits the longest draft prefix matching the model's greedy chain
    plus the model's own next token — 1..window tokens per tick per slot.
    Greedy only (``temperature`` must be 0) and exclusive with
    ``decode_quantum > 1`` (the window IS the quantum). Tokens are
    identical to the plain batcher and to ``generate`` (pinned in tests);
    rejected drafts leave garbage cache rows that the next verify window
    always overwrites before any query attends to them
    (``verify_step``'s invariant).

    ``speculative_adaptive`` — the verify-window width adapts per tick to
    the measured draft-acceptance EWMA (2..``speculative_window``), so a
    workload whose drafts stop landing stops paying wide-window verify
    FLOPs; greedy tokens are identical at any width (pinned in tests).
    The same EWMAs drive :meth:`predicted_tpot_s`, the router's
    acceptance-aware TPOT cost model.

    ``paged_kv`` — replace the dense per-slot cache with a page POOL:
    ``n_pages`` physical pages of ``page_size`` token rows (int4
    block-quantized with per-row scales by default; ``"int8"``/``False``
    for the wider codecs), a per-slot page table the attention gathers
    through, and a host-side refcounting allocator. Admission reserves
    every page a request can ever touch up front (no mid-flight
    preemption), ``register_prefix`` becomes a COPY-ON-WRITE page-table
    entry (matching requests share the prefix's full pages read-only; a
    straddling tail page is materialized privately only because the slot
    writes into it), and ``inject`` lands shipped PAGES plus local
    shared-prefix references. Requires chunked admission
    (``prefill_chunk > 0``) for local submits; single-device; greedy
    tokens are bit-identical to a dense batcher running the same KV
    codec (``kv_quant``), and the pool holds ~8× more rows per HBM byte
    than the dense f32 cache (docs/SERVING.md § Paged KV,
    docs/TUNING.md for sizing).
    """

    def __init__(
        self,
        model,
        params,
        n_slots: int = 8,
        eos_id: int | None = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        seed: int = 0,
        prompt_buckets: tuple = (32, 64, 128, 256, 512, 1024),
        decode_quantum: int = 1,
        turbo_factor: int = 0,
        prefill_chunk: int = 0,
        speculative_window: int = 0,
        speculative_ngram: int = 2,
        speculative_adaptive: bool = False,
        adaptive_quantum: int = 0,
        max_queue: int = 0,
        mesh=None,
        paged_kv=False,
        page_size: int = 16,
        n_pages: int = 0,
        preemption: bool = False,
        preempt_policy: str = "auto",
        weight_quant: str | None = "env",
    ):
        """``mesh`` — a framework mesh (``parallel.mesh.build_mesh``) makes
        serving TENSOR-PARALLEL: params are Megatron-sharded
        (``model.param_specs()``), the slot cache's (or page pool's) head
        axis shards over 'tp', and prefill/decode run head-parallel under
        shard_map with the full logits row reconstructed for sampling —
        same tokens as the single-device batcher (tests pin it).

        ``weight_quant`` — serving weight codec for the dequant-fused
        matmul path: ``"env"`` (default) reads ``DSML_WEIGHT_QUANT``
        (off unless set), ``"int8"``/``"int4"`` block-quantize the
        transformer matmul weights (``models.common.
        quantize_weights_blocked``) so they sit in HBM at ~4×/~8×
        compression and dequantize one VMEM tile at a time inside the
        Pallas matmul; ``None``/"off" serves the params as given. The
        compressed bytes are claimed in the memory ledger under
        ``weights_quant``. Single-device replicas only (the TP shard_map
        path expects plain leaves matching ``param_specs``).

        ``preemption`` (paged only) — replace up-front worst-case page
        reservation with an eviction tier: admission reserves only the
        prompt chunk grid, decode GROWS the allocation page-by-page, and
        when growth finds the pool dry the lowest-priority active slot is
        preempted — its private pages swap to host (the handoff page
        payload layout) or drop for recompute-from-prompt per
        ``preempt_policy`` ("swap" | "recompute" | "auto") — and the
        request resumes, tokens identical, once pages free. CoW-shared
        prefix pages are never evicted while shared (the refcount keeps
        the master alive; the victim only drops its reference).
        docs/SERVING.md § Paged KV has the policy rule."""
        cfg = model.config
        self.model = model
        self.mesh = mesh
        self.n_slots = n_slots
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = seed
        # sorted + deduped: _bucket picks the FIRST bucket >= len(prompt),
        # so an unsorted tuple would silently admit short prompts into the
        # largest bucket, wasting prefill compiles/compute
        self.prompt_buckets = tuple(sorted({b for b in prompt_buckets if b <= cfg.max_seq}))
        if not self.prompt_buckets:
            raise ValueError(f"no prompt bucket fits max_seq={cfg.max_seq}")
        if prefill_chunk < 0 or prefill_chunk > cfg.max_seq:
            raise ValueError(
                f"prefill_chunk must be in [0, max_seq={cfg.max_seq}], got {prefill_chunk}"
            )
        self.prefill_chunk = int(prefill_chunk)
        # the in-flight chunked admission: (request, reserved slot,
        # accumulating 1-row cache, next chunk's start position) — at most
        # one at a time; its reserved slot holds rid -2 so neither the
        # decode mask (>= 0) nor the free-slot scan (== -1) touches it.
        # (Paged mode drops the cache1 element: chunks write straight into
        # the slot's reserved pool pages — (request, slot, next start).)
        self._pending = None

        # ---- paged KV cache (docs/SERVING.md § Paged KV) ----
        # "fp" = unquantized pages (full-precision gather parity — the
        # page-table machinery alone, no codec): mode None, paged True
        self.page_quant = (None if paged_kv == "fp"
                           else model._page_mode(paged_kv))  # None|int8|int4
        self.paged = bool(paged_kv)
        self.page_size = int(page_size)
        if preemption and not self.paged:
            raise ValueError("preemption is a paged_kv eviction tier; "
                             "set paged_kv=")
        if preempt_policy not in ("swap", "recompute", "auto"):
            raise ValueError(
                f"preempt_policy must be 'swap', 'recompute', or 'auto', "
                f"got {preempt_policy!r}"
            )
        self.preemption = bool(preemption)
        self.preempt_policy = preempt_policy
        if self.paged:
            if turbo_factor or adaptive_quantum:
                raise ValueError(
                    "paged_kv composes with plain decode quanta and "
                    "speculative windows; turbo_factor/adaptive_quantum are "
                    "dense-cache escalations"
                )
            if cfg.max_seq % self.page_size:
                raise ValueError(
                    f"page_size must divide max_seq={cfg.max_seq}, got "
                    f"{self.page_size}"
                )
            self._n_pt = cfg.max_seq // self.page_size  # table entries/slot
            # 0 = parity sizing: every slot can hold max_seq rows, like the
            # dense cache — the capacity win comes from sizing it DOWN to
            # the workload (docs/TUNING.md has the accounting)
            self.n_pages = int(n_pages) or n_slots * self._n_pt + 1
            from dsml_tpu.serving.paging import PagePool

            self._pages = PagePool(self.n_pages)
            # host page table: row per slot, entry 0 (the scratch page) for
            # everything unallocated; device copy rides along per dispatch
            self._page_table = np.zeros((n_slots, self._n_pt), np.int32)
            self._slot_pages: list[list] = [[] for _ in range(n_slots)]
            # per-slot CoW accounting + preemption priority: the first
            # _slot_shared[s] entries of a slot's page list are read-only
            # shared prefix pages (never swapped — only the reference is
            # dropped on eviction); _slot_prio orders eviction victims
            self._slot_shared = np.zeros(n_slots, np.int32)
            self._slot_prio = np.zeros(n_slots, np.int64)
            # preempted-but-unfinished requests awaiting resume (FIFO;
            # resumes take precedence over fresh admissions)
            self._preempted: deque = deque()
            self.n_preemptions = 0
            self.n_swap_evictions = 0
            self.n_recompute_evictions = 0
            # flow marks dedupe per wait EPISODE (rid of the last blocked
            # head per queue) — the counter stays per-tick, but marking
            # every blocked tick would flood a stuck request's trace chain
            # and churn the bounded span buffer
            self._page_wait_rid_inject: int | None = None
            self._page_wait_rid_queue: int | None = None
            self.n_cow_copies = 0
            # pages the prefix registry holds FOREVER — the never-fits
            # checks subtract these from the reservable ceiling (a pool
            # mostly eaten by registrations must reject, not livelock)
            self._registry_pages = 0
        else:
            self.n_pages = 0

        if max_queue < 0:
            raise ValueError(f"max_queue must be >= 0 (0 = unbounded), got {max_queue}")
        # queue cap: an unbounded admission queue under overload grows
        # without limit — memory, and every queued request's latency, with
        # it. A cap makes overload an EXPLICIT signal (QueueFull +
        # serving_shed_total) the caller can act on (shed, retry elsewhere,
        # backpressure) instead of a slow collapse. 0 keeps the historical
        # unbounded behavior.
        self.max_queue = int(max_queue)
        self._obs = get_registry()  # no-op unless observability is enabled
        # serving metrics are labeled per replica so a DecodeFleet's N
        # batchers produce N distinguishable series for the cluster
        # aggregator instead of one blended stream; a standalone batcher
        # is replica "0". DecodeFleet restamps this at spawn time.
        self.obs_replica = "0"
        # worker-kind label on every serving metric: fleet merges split
        # TTFT (prefill-bound) from TPOT (decode-bound) by role. A batcher
        # is the fleet's decode worker — a standalone batcher does both
        # jobs but reports as "decode" (docs/OBSERVABILITY.md)
        self.obs_role = "decode"
        # ---- dequant-fused serving weights (docs/TUNING.md § Kernel
        # fusion) — resolve the knob, compress the params BEFORE any
        # decode program closes over them, and claim the compressed
        # bytes so the ledger's params row reconciles
        if weight_quant == "env":
            from dsml_tpu.ops.quantization import weight_quant_mode

            weight_quant = weight_quant_mode()
        if weight_quant in ("off", "none", "0", False):
            weight_quant = None
        if weight_quant is not None:
            if weight_quant not in ("int8", "int4"):
                raise ValueError(
                    f"weight_quant must be 'int8', 'int4', or None, got "
                    f"{weight_quant!r}"
                )
            if mesh is not None:
                raise ValueError(
                    "weight_quant serves single-device replicas; the TP "
                    "shard_map path expects plain param leaves matching "
                    "param_specs"
                )
            from dsml_tpu.models.common import quantize_weights_blocked
            from dsml_tpu.ops.quantization import QuantizedWeight

            params = quantize_weights_blocked(params, weight_quant)
            packed = scales = 0
            for leaf in jax.tree.leaves(
                params, is_leaf=lambda l: isinstance(l, QuantizedWeight)
            ):
                if isinstance(leaf, QuantizedWeight):
                    packed += int(leaf.qw.nbytes)
                    scales += int(leaf.qs.nbytes)
            self._wq_bytes = {"packed": packed, "scales": scales}
            from dsml_tpu.obs.memory import get_memory_ledger

            get_memory_ledger(self._obs).register_source(
                "weights_quant", self._ledger_weight_quant_bytes,
                name=f"{self.obs_replica}/{self.obs_role}/{id(self):x}",
            )
        else:
            self._wq_bytes = {}
        self.weight_quant = weight_quant
        # handed-off admissions awaiting a free slot: (Request, cache1,
        # logits row) — prefilled elsewhere, so admission is insert-only
        self._inject: deque = deque()
        self._queue: deque[Request] = deque()
        self._live: dict[int, Request] = {}  # queued or in a slot
        self._done: dict[int, Request] = {}  # retired, awaiting collect()
        self._latency: list = []  # (ttft_s, e2e_s) per retired request
        self._gaps: list = []  # consumer-visible inter-emission gap samples
        self._prefixes: list = []  # (tokens, cache1, last_logits) len-desc
        self._next_rid = 0
        # slot state (host-side numpy; device state is the cache)
        self._slot_rid = np.full(n_slots, -1, np.int64)  # -1 = free
        self._pos = np.zeros(n_slots, np.int32)  # next cache write index
        self._last_tok = np.zeros(n_slots, np.int32)
        self._slot_key = np.zeros((n_slots, 2), np.uint32)  # rid-derived PRNG keys

        if decode_quantum < 1:
            raise ValueError(f"decode_quantum must be >= 1, got {decode_quantum}")
        self.decode_quantum = decode_quantum
        if turbo_factor < 0 or turbo_factor == 1:
            raise ValueError(
                f"turbo_factor must be 0 (off) or >= 2, got {turbo_factor}"
            )
        if turbo_factor and speculative_window:
            raise ValueError(
                "turbo_factor composes with plain quanta only; the speculative "
                "window sets its own per-tick budget"
            )
        if turbo_factor and decode_quantum * turbo_factor >= cfg.max_seq:
            # submit() enforces len(prompt) + max_new <= max_seq with a
            # nonempty prompt, so remaining budget tops out at max_seq - 1:
            # a turbo quantum at or past max_seq could never engage and the
            # second program's compile would be pure waste
            raise ValueError(
                f"turbo quantum {decode_quantum * turbo_factor} >= "
                f"max_seq={cfg.max_seq} — no request could ever have that much "
                "budget remaining"
            )
        self.turbo_factor = int(turbo_factor)
        if adaptive_quantum:
            if adaptive_quantum < 2 or adaptive_quantum > cfg.max_seq:
                raise ValueError(
                    f"adaptive_quantum must be in [2, max_seq={cfg.max_seq}] "
                    f"or 0 (off), got {adaptive_quantum}"
                )
            if turbo_factor or speculative_window:
                raise ValueError(
                    "adaptive_quantum sets its own early-exit per-tick budget; "
                    "exclusive with turbo_factor and speculative_window"
                )
        self.adaptive_quantum = int(adaptive_quantum)
        # dispatch counters: observability for tests and servers (how often
        # the turbo/adaptive escalations actually engage, and what a
        # workload's host-dispatch bill actually was)
        self.n_plain_ticks = 0
        self.n_turbo_ticks = 0
        self.n_adaptive_ticks = 0
        self.n_prefill_dispatches = 0
        self.n_insert_dispatches = 0
        if speculative_window:
            if speculative_window < 2 or speculative_ngram < 1:
                raise ValueError(
                    f"speculative_window must be >= 2 (1 committed + >=1 draft) "
                    f"and speculative_ngram >= 1; got {speculative_window}, "
                    f"{speculative_ngram}"
                )
            if self.temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only (verify-by-argmax); "
                    "temperature must be 0"
                )
            if decode_quantum != 1:
                raise ValueError(
                    "speculative_window replaces decode_quantum (the window IS "
                    "the per-tick token budget); set decode_quantum=1"
                )
        self.speculative_window = int(speculative_window)
        self.speculative_ngram = int(speculative_ngram)
        if speculative_adaptive and not speculative_window:
            raise ValueError(
                "speculative_adaptive adapts the speculative window; set "
                "speculative_window >= 2"
            )
        self.speculative_adaptive = bool(speculative_adaptive)
        # speculative acceptance telemetry: per-slot EWMAs of the draft
        # acceptance rate plus a batcher-level EWMA, the measured verify
        # tick wall, and the committed-tokens-per-slot-tick EWMA — the
        # inputs to the adaptive window choice here and to the router's
        # acceptance-aware TPOT cost model (predicted_tpot_s)
        self._slot_accept = np.full(n_slots, np.nan)
        self.accept_ewma: float | None = None
        self.spec_tick_s_ewma: float | None = None
        self.commit_ewma: float | None = None
        self.n_spec_ticks = 0
        self.spec_window_used: dict[int, int] = {}  # width -> tick count
        max_seq = cfg.max_seq
        temperature = self.temperature
        top_k, top_p = self.top_k, self.top_p
        tp_axis = "tp" if mesh is not None else None
        from jax import lax

        from dsml_tpu.models.gpt2 import sample_token_logits

        def make_decode_k(k):
            """Build the k-chained slot-decode + sampling program (ONE
            dispatch). ``base_keys`` [B, 2] per-slot PRNG keys
            (rid-derived), ``steps_done`` [B] tokens already emitted per
            request (the sampler's step index — folding the ABSOLUTE step
            keeps the sampled stream identical for any k, including the
            turbo escalation). Positions clamp at max_seq-1: slots that
            retire mid-quantum keep writing their (dead) last row, which
            the next prefill overwrites."""

            def decode_k(p, c, t, pos, base_keys, steps_done):
                def body(carry, i):
                    c, t, pos = carry
                    logits, c = model.decode_step_slots(p, c, t, pos, tp_axis)
                    if temperature <= 0.0:
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    else:
                        def one(row, key, n_done):
                            k2 = jax.random.fold_in(key, n_done + i)
                            return sample_token_logits(row, k2, temperature, top_k, top_p)

                        nxt = jax.vmap(one)(logits, base_keys, steps_done)
                    return (c, nxt, jnp.minimum(pos + 1, max_seq - 1)), nxt

                (c, _, _), toks = lax.scan(body, (c, t, pos), jnp.arange(k))
                return toks, c  # toks [k, B]

            return decode_k

        decode_k = make_decode_k(decode_quantum)
        decode_turbo = (
            make_decode_k(decode_quantum * turbo_factor) if turbo_factor else None
        )

        def make_decode_k_paged(k):
            """``make_decode_k`` against the page pool: same k-chained
            scan + sampling (identical (seed, rid, step) folds — paged vs
            dense never changes WHICH token is sampled, only where its
            K/V row lives), cache writes/reads routed through the page
            table."""
            pq = self.page_quant

            def decode_k_paged(p, pool, table, t, pos, base_keys, steps_done):
                def body(carry, i):
                    pool, t, pos = carry
                    logits, pool = model.decode_step_slots_paged(
                        p, pool, table, t, pos, tp_axis, pq
                    )
                    if temperature <= 0.0:
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    else:
                        def one(row, key, n_done):
                            k2 = jax.random.fold_in(key, n_done + i)
                            return sample_token_logits(row, k2, temperature, top_k, top_p)

                        nxt = jax.vmap(one)(logits, base_keys, steps_done)
                    return (pool, nxt, jnp.minimum(pos + 1, max_seq - 1)), nxt

                (pool, _, _), toks = lax.scan(body, (pool, t, pos), jnp.arange(k))
                return toks, pool  # toks [k, B]

            return decode_k_paged

        def make_decode_until(k_max):
            """Early-exit decode loop: up to ``k_max`` chained slot-decode
            steps in ONE dispatch, stopping after the step where any ACTIVE
            slot finishes (budget reached, or EOS when configured). Returns
            (toks [k_max, B], n_steps, cache) — the host applies
            ``toks[:n_steps]``. Same token chain as ``make_decode_k``
            (sampler folds the absolute step), so tokens are identical."""
            eos = eos_id

            def decode_until(p, c, t, pos, base_keys, steps_done, remaining,
                             active):
                def cond(state):
                    _, _, _, i, stop, _ = state
                    return (i < k_max) & ~stop

                def body(state):
                    c, t, pos, i, stop, toks = state
                    logits, c = model.decode_step_slots(p, c, t, pos, tp_axis)
                    if temperature <= 0.0:
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    else:
                        def one(row, key, n_done):
                            k2 = jax.random.fold_in(key, n_done + i)
                            return sample_token_logits(
                                row, k2, temperature, top_k, top_p
                            )

                        nxt = jax.vmap(one)(logits, base_keys, steps_done)
                    toks = lax.dynamic_update_index_in_dim(toks, nxt, i, 0)
                    done = active & (i + 1 >= remaining)
                    if eos is not None:
                        done = done | (active & (nxt == eos))
                    return (c, nxt, jnp.minimum(pos + 1, max_seq - 1),
                            i + 1, jnp.any(done), toks)

                toks0 = jnp.zeros((k_max, t.shape[0]), jnp.int32)
                c, _, _, n, _, toks = lax.while_loop(
                    cond, body,
                    (c, t, pos, jnp.asarray(0, jnp.int32),
                     jnp.asarray(False), toks0),
                )
                return toks, n, c

            return decode_until

        decode_adaptive = (
            make_decode_until(adaptive_quantum) if adaptive_quantum else None
        )

        def prefill_chunk_fn(p, c, toks, start, last):
            return model.prefill_chunk(p, c, toks, start, tp_axis, last_index=last)

        # FUSED admission programs: prefill + scatter-into-slot in ONE
        # dispatch (slot is traced, so one compile serves every slot).
        # Admission cost halves: each whole-prompt admit and each chunked
        # admission's final chunk save a host round trip vs the separate
        # _insert call (which remains for the prefix-cache copy path, where
        # the stored master rows must NOT be donated)
        def prefill_insert_fn(p, cache, toks, last, slot):
            logits, c1 = model.prefill(p, toks, tp_axis, last_index=last)
            return logits, ContinuousBatcher._insert_fn(cache, c1, slot)

        def prefill_chunk_insert_fn(p, cache, c1, toks, start, last, slot):
            logits, c1 = model.prefill_chunk(
                p, c1, toks, start, tp_axis, last_index=last
            )
            return logits, ContinuousBatcher._insert_fn(cache, c1, slot)

        def verify_fn(p, c, toks, pos):  # toks [B, W], pos [B] per-slot depth
            return model.verify_step(p, c, toks, pos, tp_axis)

        if self.paged:
            pq = self.page_quant

            def chunk_paged_fn(p, pool, table, toks, start, last):
                return model.prefill_chunk_paged(
                    p, pool, table, toks, start, tp_axis, last_index=last,
                    quant=pq,
                )

            def verify_paged_fn(p, pool, table, toks, pos):
                return model.verify_step_paged(
                    p, pool, table, toks, pos, tp_axis, quant=pq
                )

            if mesh is None:
                self.params = params
                self._pool = model.init_page_pool(
                    self.n_pages, self.page_size, quant=pq
                )
                # the pool is donated every dispatch, exactly like the
                # dense cache: XLA updates the page buffers in place
                self._decode_paged = jax.jit(
                    make_decode_k_paged(decode_quantum), donate_argnums=(1,)
                )
                self._prefill_chunk_paged = jax.jit(
                    chunk_paged_fn, donate_argnums=(1,)
                )
                # jit retraces per window width, so ONE program object
                # serves the adaptive ladder (each width compiles once)
                self._verify_paged = jax.jit(
                    verify_paged_fn, donate_argnums=(1,)
                )
            else:
                # TP paged serving: the pool's HEAD axis shards over 'tp'
                # (the dense cache's sharding rule, applied to pages);
                # the page/row axes replicate their index math across
                # shards, so the page table, allocator, and host
                # scheduler are untouched — a multi-chip decode replica
                # gets the paged capacity win per chip
                from jax.sharding import NamedSharding, PartitionSpec as P

                from dsml_tpu.parallel.hybrid import shard_params

                tp_size = mesh.shape.get("tp", 1)
                n_kv = getattr(cfg, "n_kv_head", cfg.n_head)
                if n_kv % tp_size:
                    raise ValueError(
                        f"pool head count {n_kv} not divisible by tp={tp_size}"
                    )
                pspecs = model.param_specs()
                self.params = shard_params(params, mesh, pspecs)
                pool_global = model.init_page_pool(
                    self.n_pages, self.page_size, quant=pq
                )
                head_sh = NamedSharding(mesh, P(None, "tp"))
                self._pool = jax.tree.map(
                    lambda a: jax.device_put(a, head_sh), pool_global
                )
                pool_spec = jax.tree.map(lambda _: P(None, "tp"), pool_global)

                def _tp_paged_jit(fn, n_rep):
                    return jax.jit(
                        jax.shard_map(
                            fn, mesh=mesh,
                            in_specs=(pspecs, pool_spec) + (P(),) * n_rep,
                            out_specs=(P(), pool_spec),
                            check_vma=False,
                        ),
                        donate_argnums=(1,),
                    )

                self._decode_paged = _tp_paged_jit(
                    make_decode_k_paged(decode_quantum), 5
                )
                self._prefill_chunk_paged = _tp_paged_jit(chunk_paged_fn, 4)
                self._verify_paged = _tp_paged_jit(verify_paged_fn, 3)

            from dsml_tpu.serving.paging import copy_page

            # page copy / handoff install stay PLAIN jits: index-space
            # ops along the page axis, which GSPMD shards per-head for
            # free when the pool carries a tp sharding
            self._copy_page = jax.jit(copy_page, donate_argnums=(0,))

            def install_pages_fn(pool, payload, phys):
                # paged KV handoff install: shipped page payloads land
                # verbatim at the allocated physical pages
                return [
                    {key: c[key].at[phys].set(pl[key]) for key in c}
                    for c, pl in zip(pool, payload)
                ]

            self._install_pages = jax.jit(
                install_pages_fn, donate_argnums=(0,)
            )
            # pool occupancy gauges refresh at SCRAPE time (the collect
            # hook), not per tick: an idle batcher's /metrics must show
            # the pool's CURRENT state, not freeze at the last tick's
            # (the frozen-SLO-burn-gauge bug class; weakly held — the
            # hook dies with this batcher)
            self._obs.add_collect_hook(self._export_pool_gauges)
            # memory-ledger source: the pool's device bytes with the
            # live/shared/free/scratch split, re-read at every scrape and
            # postmortem (docs/OBSERVABILITY.md § Memory ledger) — weakly
            # held, so a retired batcher drops out of the ledger
            self._page_nbytes: float | None = None
            from dsml_tpu.obs.memory import get_memory_ledger

            get_memory_ledger(self._obs).register_source(
                "kv_pages", self._ledger_page_bytes,
                name=f"{self.obs_replica}/{self.obs_role}/{id(self):x}",
            )
        elif mesh is None:
            self.params = params
            self._cache = model.init_cache(n_slots)
            # the cache is donated: XLA updates it in place each tick
            # instead of allocating + copying the full [slots, H, max_seq,
            # hd] buffers per token (params are NOT donated — they serve
            # every step)
            self._decode = jax.jit(decode_k, donate_argnums=(1,))
            self._decode_turbo = (
                jax.jit(decode_turbo, donate_argnums=(1,))
                if decode_turbo else None
            )
            self._decode_adaptive = (
                jax.jit(decode_adaptive, donate_argnums=(1,))
                if decode_adaptive else None
            )
            # ONE compile serves every chunk: start/last_index stay traced
            self._prefill_chunk = jax.jit(prefill_chunk_fn, donate_argnums=(1,))
            self._prefill_insert = jax.jit(prefill_insert_fn, donate_argnums=(1,))
            self._prefill_chunk_insert = jax.jit(
                prefill_chunk_insert_fn, donate_argnums=(1, 2)
            )
            self._verify = jax.jit(verify_fn, donate_argnums=(1,))
            self._fresh_cache1 = lambda: model.init_cache(1)
            self._place_cache1 = lambda tree: jax.tree.map(jnp.asarray, tree)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from dsml_tpu.parallel.hybrid import shard_params

            tp_size = mesh.shape.get("tp", 1)
            n_heads = getattr(cfg, "n_kv_head", cfg.n_head)
            if n_heads % tp_size:
                raise ValueError(
                    f"cache head count {n_heads} not divisible by tp={tp_size}"
                )
            pspecs = model.param_specs()
            self.params = shard_params(params, mesh, pspecs)
            # global cache (full heads), head axis sharded over tp; every
            # other mesh axis replicates it
            cache_global = model.init_cache(n_slots)
            head_sh = NamedSharding(mesh, P(None, "tp"))
            self._cache = jax.tree.map(
                lambda a: jax.device_put(a, head_sh), cache_global
            )
            cache_spec = jax.tree.map(lambda _: P(None, "tp"), cache_global)
            def _tp_decode_jit(fn):
                return jax.jit(
                    jax.shard_map(
                        fn, mesh=mesh,
                        in_specs=(pspecs, cache_spec, P(), P(), P(), P()),
                        out_specs=(P(), cache_spec),
                        check_vma=False,
                    ),
                    donate_argnums=(1,),
                )

            self._decode = _tp_decode_jit(decode_k)
            self._decode_turbo = (
                _tp_decode_jit(decode_turbo) if decode_turbo else None
            )
            self._decode_adaptive = (
                jax.jit(
                    jax.shard_map(
                        decode_adaptive, mesh=mesh,
                        in_specs=(pspecs, cache_spec, P(), P(), P(), P(),
                                  P(), P()),
                        out_specs=(P(), P(), cache_spec),
                        check_vma=False,
                    ),
                    donate_argnums=(1,),
                )
                if decode_adaptive else None
            )
            self._prefill_chunk = jax.jit(
                jax.shard_map(
                    prefill_chunk_fn, mesh=mesh,
                    in_specs=(pspecs, cache_spec, P(), P(), P()),
                    out_specs=(P(), cache_spec),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
            self._prefill_insert = jax.jit(
                jax.shard_map(
                    prefill_insert_fn, mesh=mesh,
                    in_specs=(pspecs, cache_spec, P(), P(), P()),
                    out_specs=(P(), cache_spec),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
            self._prefill_chunk_insert = jax.jit(
                jax.shard_map(
                    prefill_chunk_insert_fn, mesh=mesh,
                    in_specs=(pspecs, cache_spec, cache_spec, P(), P(), P(), P()),
                    out_specs=(P(), cache_spec),
                    check_vma=False,
                ),
                donate_argnums=(1, 2),
            )
            self._verify = jax.jit(
                jax.shard_map(
                    verify_fn, mesh=mesh,
                    in_specs=(pspecs, cache_spec, P(), P()),
                    out_specs=(P(), cache_spec),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
            self._fresh_cache1 = lambda: jax.tree.map(
                lambda a: jax.device_put(a, head_sh), model.init_cache(1)
            )
            self._place_cache1 = lambda tree: jax.tree.map(
                lambda a: jax.device_put(jnp.asarray(a), head_sh), tree
            )
        self._insert = jax.jit(self._insert_fn, donate_argnums=(0,))

    @classmethod
    def for_devices(cls, model, params, devices, **kwargs):
        """Build a batcher whose replica SPANS ``devices``: more than one
        device makes the replica tensor-parallel over a ``tp=len(devices)``
        mesh (Megatron params, head-sharded cache — same tokens as the
        single-device batcher); exactly one keeps the plain single-device
        batcher. The ``DecodeFleet`` device-pool factory target: a fleet
        handing each replica a slice of chips calls this, so replica
        failover moves a MULTI-device replica's work just like a
        single-device one's. ``len(devices)`` must divide the model's head
        count (the tp-sharding rule)."""
        devices = list(devices)
        if len(devices) <= 1:
            return cls(model, params, **kwargs)
        from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec(tp=len(devices)), devices)
        return cls(model, params, mesh=mesh, **kwargs)

    @classmethod
    def from_checkpoint(cls, model, directory, step: int | None = None,
                        mesh=None, param_dtype=None, init_seed: int = 0, **kwargs):
        """Serve straight from a training checkpoint: a WEIGHTS-ONLY partial
        restore of the ``params`` subtree — the (n×-larger, n-way-sharded)
        optimizer state is never read, which is the point of the partial
        restore path (``docs/CHECKPOINT.md``). ``directory`` is a native
        checkpoint run directory (or an open ``CheckpointManager``);
        ``step=None`` loads the latest committed step. ``param_dtype``
        casts on restore (e.g. serve a bf16-trained checkpoint as f32);
        with ``mesh`` the restored weights land Megatron-sharded for the
        TP serving path. Remaining kwargs go to the constructor."""
        import jax as _jax

        from dsml_tpu.checkpoint import CheckpointManager

        manager = (directory if hasattr(directory, "restore")
                   else CheckpointManager(directory))
        template = model.init(init_seed)
        if param_dtype is not None:
            template = _jax.tree.map(
                lambda l: l.astype(param_dtype)
                if jnp.issubdtype(l.dtype, jnp.floating) else l,
                template,
            )
        if mesh is not None:
            from dsml_tpu.parallel.hybrid import shard_params

            template = shard_params(template, mesh, model.param_specs())
        params = manager.restore(
            step, template={"params": template}, partial=True
        )["params"]
        return cls(model, params, mesh=mesh, **kwargs)

    @staticmethod
    def _insert_fn(cache, cache1, slot):
        """Scatter a 1-row prefill cache into slot ``slot`` of the big
        cache (the admission write). Layout-generic over the entry keys so
        quantized caches (k/k_s/v/v_s) ride the same path."""
        return [
            {key: c[key].at[slot].set(c1[key][0]) for key in c}
            for c, c1 in zip(cache, cache1)
        ]

    # ---- request interface -----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               key_rid: int | None = None,
               trace_id: str | None = None,
               priority: int = 0) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        # the SAME validation generate applies (length budget, max_new >= 1,
        # temperature range) — duplicating it here would let the two paths'
        # contracts drift apart
        self.model._check_generate_args(
            len(prompt), max_new_tokens, self.temperature, self.top_k, self.top_p
        )
        if self.speculative_window:
            # a continuing slot verifies a full window at pos < L + max_new;
            # its last row (pos + W - 1) must stay inside the cache
            w = self.speculative_window
            if len(prompt) + max_new_tokens + w - 1 > self.model.config.max_seq:
                raise ValueError(
                    f"prompt ({len(prompt)}) + max_new ({max_new_tokens}) + "
                    f"speculative_window-1 ({w - 1}) must fit max_seq="
                    f"{self.model.config.max_seq}"
                )
        if self.paged:
            if not self.prefill_chunk:
                raise ValueError(
                    "paged local admission requires prefill_chunk > 0 "
                    "(decode-only paged workers admit via inject)"
                )
            if not self._chunk_grid_fits(len(prompt)):
                raise ValueError(
                    f"prompt length {len(prompt)} exceeds the chunk grid for "
                    f"max_seq={self.model.config.max_seq} (paged admission "
                    "has no bucketed fallback)"
                )
            # never-fits check against the RESERVABLE ceiling: total pages
            # minus scratch minus the registry's permanent holdings, with
            # a matched prefix's shared full pages credited — a request
            # that could only livelock at the FIFO head must fail HERE
            pre = self._prefixes and self._match_prefix(prompt)
            p_len = len(pre[0]) if pre else 0
            need = self._reserve_rows(len(prompt), max_new_tokens, p_len,
                                      worst_case=True)
            n_private = -(-need // self.page_size) - p_len // self.page_size
            ceiling = self.n_pages - 1 - self._registry_pages
            if n_private > ceiling:
                raise ValueError(
                    f"request needs {n_private} private pages but only "
                    f"{ceiling} are ever reservable ({self._registry_pages} "
                    "held by the prefix registry); raise n_pages"
                )
        elif not self._chunk_grid_fits(len(prompt)):
            # whole-prompt bucketed admission → reject at submit, not admit
            _bucket(len(prompt), self.prompt_buckets)
        if self.max_queue and len(self._queue) >= self.max_queue:
            # shed AFTER validation: a malformed request is the caller's
            # bug (ValueError), a full queue is the deployment's state
            self._shed()
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
                      submitted_at=time.monotonic(), key_rid=key_rid,
                      trace_id=trace_id, priority=int(priority))
        self._queue.append(req)
        self._live[rid] = req
        return rid

    def _shed(self) -> None:
        self._obs.counter(
            "serving_shed_total",
            "requests rejected by the queue cap",
            labels=("replica", "role"),
        ).inc(replica=self.obs_replica, role=self.obs_role)
        raise QueueFull(
            f"admission queue at its cap ({self.max_queue} waiting); "
            "request shed — retry on another replica or back off"
        )

    def inject(self, prompt, max_new_tokens: int, cache1=None,
               logits_row=None, key_rid: int | None = None,
               submitted_at: float | None = None, *,
               kv_pages=None, page_size: int | None = None,
               prefix_rows: int = 0, trace_id: str | None = None) -> int:
        """Admit a request whose PREFILL already ran elsewhere — the
        decode-worker half of the disaggregated fleet's KV handoff
        (``dsml_tpu.serving.handoff``). ``cache1`` is the 1-row KV cache a
        ``PrefillWorker`` (or this class's own chunked-prefill path)
        produced for the whole prompt; ``logits_row`` the next-token
        logits at the prompt's last position. Admission costs ONE insert
        scatter (no prefill compute on this worker); the first token
        samples from ``logits_row`` under the identical
        (seed, ``key_rid``, step) fold a local admission would use, so
        tokens are bit-identical to submitting the prompt here (pinned in
        tests). ``submitted_at`` carries the ORIGINAL submit time so the
        admission-latency histogram reports true TTFT, queue + prefill +
        handoff included. Sheds with :class:`QueueFull` at ``max_queue``
        like :meth:`submit` (the router retries on another replica).

        A PAGED worker admits a paged handoff instead: ``kv_pages`` is
        the shipped page payload (per-layer dicts with a leading
        shipped-page axis, the pool's own entry layout), ``page_size``
        the sender's (must match), and ``prefix_rows`` the leading rows
        NOT shipped because this worker shares its own registered prefix
        pages for them (copy-on-write — validated here against the local
        registry so a mismatch fails at the fleet edge, not inside a
        tick)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        self.model._check_generate_args(
            len(prompt), max_new_tokens, self.temperature, self.top_k, self.top_p
        )
        cfg = self.model.config
        if self.paged:
            if kv_pages is None:
                raise ValueError(
                    "paged decode worker: inject needs kv_pages= (a dense "
                    "cache1 cannot land in a page pool)"
                )
            if page_size != self.page_size:
                raise ValueError(
                    f"handoff pages are {page_size} rows, this pool's are "
                    f"{self.page_size} — prefill and decode workers must "
                    "share the page size"
                )
            if len(kv_pages) != cfg.n_layer:
                raise ValueError(
                    f"handoff has {len(kv_pages)} layers, model has "
                    f"{cfg.n_layer}"
                )
            ref = self._pool[0]
            for key in ref:
                arr = kv_pages[0].get(key)
                if arr is None or tuple(arr.shape[1:]) != tuple(ref[key].shape[1:]):
                    raise ValueError(
                        f"handoff page entry {key!r} is "
                        f"{None if arr is None else tuple(arr.shape)}; pool "
                        f"pages are {tuple(ref[key].shape)} — quant modes "
                        "must match"
                    )
            if prefix_rows < 0 or prefix_rows % self.page_size or \
                    prefix_rows > len(prompt):
                raise ValueError(
                    f"prefix_rows={prefix_rows} must be a multiple of "
                    f"page_size={self.page_size} within the prompt"
                )
            if prefix_rows:
                # fail at the fleet edge if no local registration covers
                # the shared rows (the router replicates registrations, so
                # this is a deployment bug, not a runtime state)
                self._registered_prefix_pages(prompt, prefix_rows)
            n_ship = int(kv_pages[0]["k"].shape[0])
            rows = self._handoff_rows(len(prompt), max_new_tokens,
                                      prefix_rows, n_ship, worst_case=True)
            n_private = (-(-rows // self.page_size)
                         - prefix_rows // self.page_size)
            ceiling = self.n_pages - 1 - self._registry_pages
            if n_private > ceiling:
                raise ValueError(
                    f"handoff needs {n_private} private pages but only "
                    f"{ceiling} are ever reservable ({self._registry_pages} "
                    "held by the prefix registry); raise n_pages"
                )
        else:
            if kv_pages is not None:
                raise ValueError(
                    "dense decode worker: got kv_pages= (paged handoffs "
                    "need a paged_kv batcher)"
                )
            if len(cache1) != cfg.n_layer:
                raise ValueError(
                    f"handoff cache has {len(cache1)} layers, model has "
                    f"{cfg.n_layer}"
                )
            k = cache1[0]["k"]
            if k.shape[0] != 1 or k.shape[2] != cfg.max_seq:
                raise ValueError(
                    f"handoff cache rows are {tuple(k.shape)}; expected "
                    f"(1, heads, max_seq={cfg.max_seq}, ...) — prefill and "
                    "decode workers must share the model config"
                )
        if self.max_queue and len(self._inject) >= self.max_queue:
            self._shed()
        rid = self._next_rid
        self._next_rid += 1
        req = Request(
            rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
            submitted_at=(time.monotonic() if submitted_at is None
                          else submitted_at),
            key_rid=key_rid, trace_id=trace_id,
        )
        self._live[rid] = req
        ctx = req.trace_ctx()
        if ctx is not None and self._obs.enabled:
            from dsml_tpu.obs import get_tracer

            # the handoff landed on this decode worker: a flow step on
            # the decode lane links the prefill host's handoff span to
            # the admission that follows
            get_tracer().flow("decode_inject", ctx, phase="step",
                              rid=rid, replica=self.obs_replica)
        payload = (kv_pages, int(prefix_rows)) if self.paged else cache1
        self._inject.append((req, payload, np.asarray(logits_row).reshape(-1)))
        return rid

    def _admit_injected(self, emitted: dict) -> None:
        """Admit handed-off requests into free slots: insert the prefilled
        rows and run the shared admission epilogue — ONE dispatch, zero
        prefill compute (an in-process handoff's device rows pass through
        ``_place_cache1`` untouched, so the host never copies them).
        Handoffs admit BEFORE queued prompts: they already paid their
        prefill, so waiting behind local prefill work would squander the
        disaggregation win. Paged handoffs reserve + install PAGES
        instead: shared prefix rows resolve to this worker's own
        registered prefix pages (refcount++, zero bytes moved), shipped
        pages land verbatim at freshly allocated physical pages, and the
        decode budget's remaining pages come from the free list — an
        admission that can't reserve waits in the inject queue."""
        from dsml_tpu.serving.paging import pages_for

        while self._inject:
            free = np.flatnonzero(self._slot_rid == -1)
            if len(free) == 0:
                return
            if not self.paged:
                req, cache1, logits_row = self._inject.popleft()
                slot = int(free[0])
                self.n_insert_dispatches += 1
                self._cache = self._insert(
                    self._cache, self._place_cache1(cache1), jnp.int32(slot)
                )
                self._finish_admission(req, slot, logits_row, emitted)
                continue
            req, (payload, prefix_rows), logits_row = self._inject[0]  # peek
            slot = int(free[0])
            n_ship = int(payload[0]["k"].shape[0])
            rows = self._handoff_rows(len(req.prompt), req.max_new_tokens,
                                      prefix_rows, n_ship)
            n_full = prefix_rows // self.page_size
            n_private = pages_for(rows, self.page_size) - n_full
            if not self._pages.can_alloc(n_private):
                from dsml_tpu.serving.paging import note_page_wait

                first = self._page_wait_rid_inject != req.rid
                self._page_wait_rid_inject = req.rid
                note_page_wait(self._obs, self.obs_replica, self.obs_role,
                               trace=req.trace_ctx() if first else None)
                return  # pool full: the handoff waits for retirements
            shared = (self._registered_prefix_pages(req.prompt, prefix_rows)
                      if prefix_rows else [])
            self._pages.share(shared)
            private = self._pages.alloc(n_private)
            self._inject.popleft()
            self._slot_pages[slot] = shared + private
            # the CoW boundary must ride along: eviction treats the first
            # _slot_shared entries as reference-only (never swapped), so an
            # injected slot without it would swap out REGISTRY pages and
            # resume as if they were its own private allocation
            self._slot_shared[slot] = len(shared)
            self._page_table[slot, :] = 0
            self._page_table[slot, : len(shared) + len(private)] = shared + private
            if n_ship:
                payload_dev = [
                    {key: jnp.asarray(arr) for key, arr in layer.items()}
                    for layer in payload
                ]
                self.n_insert_dispatches += 1
                self._pool = self._install_pages(
                    self._pool, payload_dev,
                    jnp.asarray(private[:n_ship], jnp.int32),
                )
            self._finish_admission(req, slot, logits_row, emitted)

    def _registered_prefix_pages(self, prompt: np.ndarray,
                                 prefix_rows: int) -> list:
        """The first ``prefix_rows // page_size`` pages of a registered
        prefix agreeing with ``prompt`` on its first ``prefix_rows``
        tokens. ANY agreeing registration serves: a page's bytes depend
        only on the tokens at and before its rows (causality) and the
        codec is deterministic, so every agreeing prefix holds identical
        bytes there. ``inject`` validated a match exists."""
        n_full = prefix_rows // self.page_size
        for ptoks, ppages, _ in self._prefixes:
            if len(ptoks) >= prefix_rows and len(ppages) >= n_full and \
                    np.array_equal(ptoks[:prefix_rows], prompt[:prefix_rows]):
                return [int(p) for p in ppages[:n_full]]
        raise RuntimeError(
            f"no registered prefix covers the handoff's {prefix_rows} shared "
            "rows — inject validation should have rejected it"
        )

    def register_prefix(self, tokens) -> None:
        """Precompute and retain the KV rows + next-token logits for a
        shared prompt head (a system prompt). Later ``submit``s whose
        prompt starts with the longest registered prefix admit by copying
        these rows and chunk-prefilling only the suffix. Registration is
        a blocking setup call (it runs the prefix's chunked prefill).

        On a PAGED batcher the registration IS a page-table entry: the
        prefix chunk-prefills into pool pages held by the registry
        (refcount 1, forever), and matching admissions SHARE those pages
        read-only instead of copying rows — copy-on-write materializes
        at most the one page a straddling prefix tail makes the slot
        write into. A paged decode-only worker (``prefill_chunk=0``) may
        register too — that is how the fleet's decode side holds the
        prefix pages its paged handoffs reference."""
        if self.paged:
            self._register_prefix_paged(tokens)
            return
        if not self.prefill_chunk:
            raise ValueError("prefix caching requires prefill_chunk > 0")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = len(tokens)
        if n < 1:
            raise ValueError("empty prefix")
        if not self._chunk_grid_fits(n):
            raise ValueError(
                f"prefix length {n} exceeds the chunk grid for max_seq="
                f"{self.model.config.max_seq}"
            )
        c = self.prefill_chunk
        cache1 = self._fresh_cache1()
        logits = None
        for start in range(0, n, c):
            end = min(start + c, n)
            padded = np.zeros((1, c), np.int32)
            padded[0, : end - start] = tokens[start:end]
            last_local = (n - 1) - start if end >= n else c - 1
            logits, cache1 = self._prefill_chunk(
                self.params, cache1, jnp.asarray(padded),
                jnp.int32(start), jnp.int32(last_local),
            )
        self._prefixes.append((tokens, cache1, np.asarray(logits[0])))
        self._prefixes.sort(key=lambda p: -len(p[0]))  # longest match wins

    def _register_prefix_paged(self, tokens) -> None:
        """Chunk-prefill a prefix into registry-held pool pages. The
        chunk size is ``prefill_chunk`` when local admission runs here,
        else ``page_size`` — a quantized pool makes chunk chaining
        CHUNK-SIZE-INVARIANT (every query reads every key quantized), so
        pages registered with one chunk size are bit-identical to a
        prefill worker's at another (pinned in tests). Pages the padded
        final chunk touches beyond the prefix (pad garbage) are released
        right back — the registry retains exactly ⌈n/page_size⌉ pages."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = len(tokens)
        if n < 1:
            raise ValueError("empty prefix")
        c = self.prefill_chunk or self.page_size
        if -(-n // c) * c > self.model.config.max_seq:
            raise ValueError(
                f"prefix length {n} exceeds the chunk grid for max_seq="
                f"{self.model.config.max_seq}"
            )
        from dsml_tpu.serving.paging import prefill_prefix_into_pages

        pages, logits, self._pool = prefill_prefix_into_pages(
            self._prefill_chunk_paged, self.params, self._pool, self._pages,
            tokens, c, self.page_size, self._n_pt,
        )
        self._registry_pages += len(pages)
        self._prefixes.append((tokens, pages, logits))
        self._prefixes.sort(key=lambda p: -len(p[0]))  # longest match wins

    def _match_prefix(self, prompt: np.ndarray):
        """Longest registered prefix that heads ``prompt`` AND whose
        suffix chunk grid stays inside the cache; None otherwise."""
        L = len(prompt)
        c = self.prefill_chunk
        max_seq = self.model.config.max_seq
        for ptoks, pcache, plogits in self._prefixes:
            p = len(ptoks)
            if p > L or not np.array_equal(prompt[:p], ptoks):
                continue
            if p < L and p + (-(-(L - p) // c)) * c > max_seq:
                continue  # padded suffix grid would overrun the cache
            return ptoks, pcache, plogits
        return None

    @property
    def n_active(self) -> int:
        return int((self._slot_rid >= 0).sum())

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def n_pending(self) -> int:
        """Chunked admissions currently mid-prefill (0 or 1) — queued in
        neither ``n_queued`` nor ``n_active``; drain loops must check all
        three (``run`` does)."""
        return 0 if self._pending is None else 1

    @property
    def n_injected(self) -> int:
        """Handed-off admissions waiting for a free slot (:meth:`inject`)
        — a fourth drain-loop term alongside queued/active/pending."""
        return len(self._inject)

    @property
    def n_preempted(self) -> int:
        """Evicted-but-unfinished requests awaiting resume (the paged
        ``preemption`` tier) — the fifth drain-loop term; 0 elsewhere."""
        return len(self._preempted) if (self.paged and self.preemption) else 0

    # ---- scheduling ------------------------------------------------------------

    def _request_key(self, rid: int):
        """The rid-derived base PRNG key — THE one derivation shared by the
        host sampler, the slot-key table, and (folded with the step index)
        the in-scan sampler; the quantum/slot-independence guarantees rest
        on all samplers folding the identical (seed, rid, step) sequence."""
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), rid)

    def _req_key(self, req: Request):
        """:meth:`_request_key` under the request's SAMPLER identity —
        ``key_rid`` when the router stamped one (fleet-wide rid), else the
        local rid. Every sampler site derives through here so a handed-off
        request's token stream matches the reference batcher's exactly."""
        return self._request_key(req.rid if req.key_rid is None else req.key_rid)

    def _sample(self, logits: np.ndarray, req: Request) -> int:
        if self.temperature <= 0.0:
            return int(np.argmax(logits))
        from dsml_tpu.models.gpt2 import sample_token_logits

        key = jax.random.fold_in(self._req_key(req), len(req.tokens))
        return int(sample_token_logits(
            jnp.asarray(logits), key, self.temperature, self.top_k, self.top_p
        ))

    def _chunk_grid_fits(self, prompt_len: int) -> bool:
        """True when the chunked path serves this prompt: chunking is on
        and the padded chunk grid ceil(L/C)·C stays inside max_seq (the
        final chunk is right-padded to C, and its padded K/V rows must not
        wrap past the cache end). With C dividing max_seq — every default —
        this is simply L <= max_seq."""
        c = self.prefill_chunk
        if c <= 0:
            return False
        return -(-prompt_len // c) * c <= self.model.config.max_seq

    def _handoff_rows(self, prompt_len: int, max_new: int, prefix_rows: int,
                      n_ship: int, worst_case: bool = False) -> int:
        """Rows a paged HANDOFF admission must reserve pages for: the
        decode budget (+ speculative overhang) or the shipped+shared page
        grid, whichever is larger — THE one formula, shared by inject's
        capacity validation and the actual admission reservation so the
        two can never disagree. With ``preemption`` the admission
        reserves only the landing grid (shipped + shared pages) and the
        decode budget grows page-by-page."""
        base = prompt_len + max_new
        if self.speculative_window:
            base += self.speculative_window - 1
        landing = prefix_rows + n_ship * self.page_size
        if self.preemption and not worst_case:
            return max(prompt_len, landing)
        return max(base, landing)

    def _reserve_rows(self, prompt_len: int, max_new: int,
                      prefix_len: int, worst_case: bool = False) -> int:
        """Rows a paged admission must reserve pages for — everything the
        request can EVER write: the padded prefill chunk grid (pad rows of
        the final chunk land in pages too), the decode budget, and the
        speculative verify window's overhang. Reserving up front is what
        makes decode/verify allocation-free mid-flight (docs/SERVING.md).

        With ``preemption`` only the CHUNK GRID reserves (what prefill
        itself writes); the decode budget and verify overhang grow
        page-by-page under ``_ensure_decode_pages``, and pressure evicts
        instead of deadlocking — admission tracks current demand, not the
        worst case. ``worst_case=True`` (submit's never-fits check)
        always returns the full footprint: eviction cannot shrink ONE
        request's own eventual live set, so a request whose footprint
        exceeds the reservable ceiling must still fail at submit."""
        base = prompt_len + max_new
        if self.speculative_window:
            base += self.speculative_window - 1
        c = self.prefill_chunk or self.page_size
        grid_end = prefix_len + -(-(prompt_len - prefix_len) // c) * c \
            if prompt_len > prefix_len else prompt_len
        if self.preemption and not worst_case:
            return min(self.model.config.max_seq, grid_end)
        return min(self.model.config.max_seq, max(base, grid_end))

    def _assign_slot_pages(self, slot: int, plan) -> None:
        """Install an admission plan's pages as ``slot``'s page table (and
        run its CoW straddle copy, counting it)."""
        self._slot_pages[slot] = list(plan.pages)
        self._slot_shared[slot] = plan.n_shared
        self._page_table[slot, :] = 0
        self._page_table[slot, : len(plan.pages)] = plan.pages
        if plan.copy is not None:
            src, dst = plan.copy
            self._pool = self._copy_page(
                self._pool, jnp.int32(src), jnp.int32(dst)
            )
            self.n_cow_copies += 1
            self._obs.counter(
                "serving_cow_copies_total",
                "prefix pages materialized privately on first write",
                labels=("replica", "role"),
            ).inc(replica=self.obs_replica, role=self.obs_role)

    def _decode_table(self) -> np.ndarray:
        """The page table a decode/verify dispatch may see: ACTIVE slots'
        rows only. A pending chunked admission's slot already owns its
        reserved pages (the chunk program writes them), but the decode
        program also writes a (masked, never-read) garbage row for every
        non-active slot — routed to the scratch page here, so a decode
        tick interleaving with a mid-flight admission can never clobber
        its freshly prefilled rows (the paged twin of the dense path's
        separate accumulating cache1; regression-pinned)."""
        return np.where((self._slot_rid >= 0)[:, None], self._page_table, 0)

    def _free_slot_pages(self, slot: int) -> None:
        """Release a slot's pages back to the pool (retire/abandon path);
        its table row points back at the scratch page so the decode
        program's dead-slot writes stay harmless. No-op for dense."""
        if not self.paged:
            return
        pages = self._slot_pages[slot]
        if pages:
            self._pages.release(pages)
        self._slot_pages[slot] = []
        self._slot_shared[slot] = 0
        self._page_table[slot, :] = 0

    # ---- eviction-based preemption (paged preemption=True) ---------------------

    def _pick_victim(self, exclude: int | None = None) -> int | None:
        """The eviction order: lowest priority first, youngest (highest
        rid) within a priority — FIFO fairness degrades last. ``exclude``
        shields the slot whose growth triggered the pressure (it preempts
        itself only when nothing else is left)."""
        best = None
        for slot in np.flatnonzero(self._slot_rid >= 0):
            s = int(slot)
            if s == exclude:
                continue
            key = (self._slot_prio[s], -self._slot_rid[s])
            if best is None or key < best[0]:
                best = (key, s)
        return None if best is None else best[1]

    def _preempt_kind(self, req) -> str:
        """The swap-vs-recompute rule (docs/SERVING.md § Paged KV).
        "auto": a victim still at its first token holds only prompt-grid
        pages that chunked prefill reproduces at full throughput (and may
        re-hit the prefix cache) — RECOMPUTE, skip the host round trip;
        past that, swapping the live bytes beats re-running prefill over
        prompt + emitted rows. Both paths resume with identical tokens
        (quantized chunk chaining is chunk-size-invariant, so recomputed
        rows are bit-identical to the evicted ones — the PR 11 property
        the recompute path rests on)."""
        if self.preempt_policy != "auto":
            return self.preempt_policy
        return "recompute" if len(req.tokens) <= 1 else "swap"

    def _evict_slot(self, slot: int) -> None:
        """Preempt ``slot``: private pages swap to host (the handoff page
        payload layout — ``paging.gather_pages``) or drop for recompute,
        ALL page references release (a CoW-shared prefix page just loses
        this reference; the refcount keeps the registry master alive —
        shared pages are NEVER evicted while shared), and the request
        joins the resume queue. The consumer sees a longer inter-emission
        gap, never different tokens."""
        from dsml_tpu.serving.paging import gather_pages

        req = self._live[int(self._slot_rid[slot])]
        pages = self._slot_pages[slot]
        n_shared = int(self._slot_shared[slot])
        private = pages[n_shared:]
        kind = self._preempt_kind(req)
        entry = {
            "req": req,
            "pos": int(self._pos[slot]),
            "last_tok": int(self._last_tok[slot]),
            "shared_rows": n_shared * self.page_size,
            "kind": kind,
        }
        if kind == "swap":
            entry["pages_host"] = gather_pages(self._pool, private)
            self.n_swap_evictions += 1
        else:
            self.n_recompute_evictions += 1
        self._slot_rid[slot] = -1
        self._free_slot_pages(slot)
        self._preempted.append(entry)
        self.n_preemptions += 1
        if self._obs.enabled:
            from dsml_tpu.obs import flight_recorder

            self._obs.counter(
                "serving_preemptions_total",
                "slots evicted under page-pool pressure",
                labels=("kind", "replica", "role"),
            ).inc(kind=kind, replica=self.obs_replica, role=self.obs_role)
            extra = {"trace_id": req.trace_id} if req.trace_id else {}
            # the pressure that forced this eviction, measured-headroom
            # first (memory_pressure) — a postmortem shows whether the
            # chip or merely the pool sizing was the constraint
            flight_recorder.record(
                "serving_preempt", rid=req.rid, kind=kind,
                pos=entry["pos"],
                pressure=round(self.memory_pressure(), 4), **extra,
            )

    def _ensure_decode_pages(self, active, width: int):
        """Preemption-mode page GROWTH: before a decode/verify dispatch,
        every participating slot must own pages covering its next
        ``width`` write rows. When the pool is dry, evict (lowest
        priority, youngest first) until the growth fits — the growing
        slot itself is preempted only when no other victim remains.
        Returns the slots still active (victims drop out); non-preemption
        batchers pass through untouched (their reservation covered
        everything up front)."""
        if not (self.paged and self.preemption):
            return active
        max_seq = self.model.config.max_seq
        kept = []
        for slot in active:
            s = int(slot)
            if self._slot_rid[s] < 0:
                continue  # already evicted as a victim this pass
            last_row = min(int(self._pos[s]) + width - 1, max_seq - 1)
            n_entries = last_row // self.page_size + 1
            while len(self._slot_pages[s]) < n_entries:
                want = n_entries - len(self._slot_pages[s])
                if self._pages.can_alloc(want):
                    start_i = len(self._slot_pages[s])
                    new = self._pages.alloc(want)
                    self._slot_pages[s].extend(new)
                    self._page_table[s, start_i : start_i + want] = new
                    continue
                victim = self._pick_victim(exclude=s)
                if victim is None:
                    # nothing else holds pages: this slot yields and
                    # resumes when retirements free the pool (submit's
                    # worst-case never-fits check guarantees it CAN)
                    self._evict_slot(s)
                    break
                self._evict_slot(int(victim))
            if self._slot_rid[s] >= 0:
                kept.append(s)
        return [s for s in kept if self._slot_rid[s] >= 0]

    def _try_resume(self, entry: dict, slot: int) -> bool:
        """Re-admit one preempted request into ``slot``. Swap: re-share
        the registered prefix pages, allocate fresh private pages, land
        the host copy verbatim (the handoff install scatter), restore the
        decode state — bit-identical rows, zero recompute. Recompute:
        reserve the re-prefill grid and stage a pending chunked admission
        over prompt + emitted tokens (all but the last, which is the next
        decode input) — chunk-size invariance makes the rebuilt rows
        bit-identical to the evicted ones. Returns False when the pool
        cannot serve the resume yet (it keeps its queue spot; resumes
        precede fresh admissions)."""
        from dsml_tpu.serving.paging import pages_for

        req = entry["req"]
        pos = entry["pos"]
        shared_rows = entry["shared_rows"]
        n_full = shared_rows // self.page_size
        if entry["kind"] == "swap":
            payload = entry["pages_host"]
            n_private = int(payload[0]["k"].shape[0])
            if not self._pages.can_alloc(n_private):
                return False
            shared = (self._registered_prefix_pages(req.prompt, shared_rows)
                      if shared_rows else [])
            self._pages.share(shared)
            private = self._pages.alloc(n_private)
            pages = shared + private
            self._slot_pages[slot] = pages
            self._slot_shared[slot] = n_full
            self._page_table[slot, :] = 0
            self._page_table[slot, : len(pages)] = pages
            if n_private:
                payload_dev = [
                    {key: jnp.asarray(arr) for key, arr in layer.items()}
                    for layer in payload
                ]
                self.n_insert_dispatches += 1
                self._pool = self._install_pages(
                    self._pool, payload_dev,
                    jnp.asarray(private, jnp.int32),
                )
            self._restore_slot(req, slot, pos, entry["last_tok"])
            return True
        # recompute: re-prefill prompt + tokens[:-1] (rows [0, pos)) from
        # the shared prefix boundary; the final chunk's logits are
        # discarded — the request already emitted its next input token
        c = self.prefill_chunk or self.page_size
        grid_end = shared_rows + -(-(pos - shared_rows) // c) * c
        grid_end = min(grid_end, self.model.config.max_seq)
        n_private = pages_for(grid_end, self.page_size) - n_full
        if not self._pages.can_alloc(n_private):
            return False
        shared = (self._registered_prefix_pages(req.prompt, shared_rows)
                  if shared_rows else [])
        self._pages.share(shared)
        private = self._pages.alloc(n_private)
        pages = shared + private
        self._slot_pages[slot] = pages
        self._slot_shared[slot] = n_full
        self._page_table[slot, :] = 0
        self._page_table[slot, : len(pages)] = pages
        if pos == shared_rows:
            # every written row lives in shared registry pages (an
            # exact-hit admission evicted before writing): nothing to
            # recompute — reoccupy directly
            self._restore_slot(req, slot, pos, entry["last_tok"])
            return True
        seq = np.concatenate(
            [req.prompt, np.asarray(req.tokens[:-1], np.int32)]
        )
        assert len(seq) == pos, (len(seq), pos)
        self._slot_rid[slot] = -2  # reserved: not free, not decoding
        self._pending = (req, slot, shared_rows, seq,
                         {"pos": pos, "last_tok": entry["last_tok"]})
        return True

    def _restore_slot(self, req, slot: int, pos: int, last_tok: int) -> None:
        """Reoccupy ``slot`` with a resumed request's decode state (no
        emission, no first-token sample — those already happened)."""
        self._slot_rid[slot] = req.rid
        self._pos[slot] = pos
        self._last_tok[slot] = last_tok
        self._slot_key[slot] = np.asarray(self._req_key(req))
        self._slot_accept[slot] = np.nan
        self._slot_prio[slot] = req.priority
        if self._obs.enabled:
            from dsml_tpu.obs import flight_recorder

            extra = {"trace_id": req.trace_id} if req.trace_id else {}
            flight_recorder.record("serving_resume", rid=req.rid, pos=pos,
                                   **extra)

    @property
    def free_pages(self) -> int:
        return self._pages.free_pages if self.paged else 0

    @property
    def used_pages(self) -> int:
        return self._pages.used_pages if self.paged else 0

    @property
    def shared_pages(self) -> int:
        return self._pages.shared_pages if self.paged else 0

    def _occupy(self, req: Request, slot: int, tok: int) -> None:
        """Install an admitted (not-yet-finished) request into its slot."""
        self._slot_rid[slot] = req.rid
        self._pos[slot] = len(req.prompt)
        self._last_tok[slot] = tok
        self._slot_key[slot] = np.asarray(self._req_key(req))
        self._slot_accept[slot] = np.nan  # a fresh request, a fresh EWMA
        if self.paged:
            self._slot_prio[slot] = req.priority

    def _finish_admission(self, req: Request, slot: int, logits_row, emitted: dict) -> None:
        """THE admission epilogue — shared by whole-prompt, chunked, and
        exact-prefix admissions so the bookkeeping cannot drift: sample the
        first token, stamp TTFT, emit, then retire (slot stays free) or
        occupy."""
        tok = self._sample(np.asarray(logits_row), req)
        req.tokens.append(tok)
        req.first_token_at = time.monotonic()
        if self._obs.enabled:
            # admission latency = queue wait + prefill: the serving-side
            # TTFT, as a histogram the /metrics endpoint can expose live.
            # The sample carries the request's trace_id as an EXEMPLAR, so
            # a tail bucket resolves to the trace that landed in it
            admission_ms = (req.first_token_at - req.submitted_at) * 1e3
            self._obs.histogram(
                "serving_admission_ms", "submit→first-token latency",
                labels=("replica", "role"),
            ).observe(admission_ms, exemplar=req.trace_id,
                      replica=self.obs_replica, role=self.obs_role)
            from dsml_tpu.obs import flight_recorder, get_tracer

            extra = {"trace_id": req.trace_id} if req.trace_id else {}
            flight_recorder.record(
                "serving_admit", rid=req.rid, prompt_len=len(req.prompt),
                admission_ms=round(admission_ms, 3), **extra,
            )
            ctx = req.trace_ctx()
            if ctx is not None:
                get_tracer().instant(
                    "serving_first_token", trace_id=req.trace_id,
                    rid=req.rid, admission_ms=round(admission_ms, 3),
                    replica=self.obs_replica,
                )
        emitted[req.rid] = [tok]
        if self._finished(req, tok):
            self._retire(req)
            self._slot_rid[slot] = -1  # release any reservation
            self._free_slot_pages(slot)
            return
        self._occupy(req, slot, tok)

    def _admit_full(self, req: Request, slot: int, emitted: dict) -> None:
        """Whole-prompt bucketed prefill + cache insert + first sampled
        token. A request that finishes AT prefill (budget 1 or immediate
        EOS) never occupies the slot."""
        L = len(req.prompt)
        bucket = _bucket(L, self.prompt_buckets)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :L] = req.prompt
        # fused prefill+insert: one dispatch per admission
        logits, self._cache = self._prefill_insert(
            self.params, self._cache, jnp.asarray(padded), jnp.int32(L - 1),
            jnp.int32(slot),
        )
        self.n_prefill_dispatches += 1
        self._finish_admission(req, slot, logits[0], emitted)

    def _admit(self) -> dict[int, list]:
        """Fill free slots from the queue (whole-prompt admission path).
        Returns {rid: [first token]} for every admission — step() merges it
        so streaming consumers see token 1 too."""
        emitted: dict[int, list] = {}
        for slot in np.flatnonzero(self._slot_rid == -1):
            while self._queue and self._slot_rid[slot] == -1:
                self._admit_full(self._queue.popleft(), int(slot), emitted)
        return emitted

    def _advance_pending(self, emitted: dict) -> bool:
        """Run ONE chunk of the in-flight chunked admission. On the final
        chunk: sample the first token, insert the accumulated cache into
        the reserved slot, and occupy (or retire) it. Returns True when the
        admission completed this call."""
        req, slot, cache1, start = self._pending
        c = self.prefill_chunk
        L = len(req.prompt)
        end = min(start + c, L)
        padded = np.zeros((1, c), np.int32)
        padded[0, : end - start] = req.prompt[start:end]
        is_last = end >= L
        last_local = (L - 1) - start if is_last else c - 1
        if not is_last:
            logits, cache1 = self._prefill_chunk(
                self.params, cache1, jnp.asarray(padded),
                jnp.int32(start), jnp.int32(last_local),
            )
            self.n_prefill_dispatches += 1
            self._pending = (req, slot, cache1, start + c)
            return False
        # final chunk: fused chunk-prefill + insert — one dispatch
        logits, self._cache = self._prefill_chunk_insert(
            self.params, self._cache, cache1, jnp.asarray(padded),
            jnp.int32(start), jnp.int32(last_local), jnp.int32(slot),
        )
        self.n_prefill_dispatches += 1
        self._pending = None
        self._finish_admission(req, slot, logits[0], emitted)
        return True

    def _admit_chunked(self) -> dict[int, list]:
        """Chunked admission pass: advance the in-flight admission by ONE
        chunk; when an admission completes (short prompts complete in one
        chunk), keep admitting from the queue, so cold-start still fills
        every free slot in a single tick. The moment a LONG prompt's chunk
        finishes without completing the admission, the pass yields — decode
        quanta run between its remaining chunks (no head-of-line stall)."""
        emitted: dict[int, list] = {}
        while True:
            if self._pending is not None:
                if not self._advance_pending(emitted):
                    return emitted  # long admission mid-flight: decode now
                continue  # completed → maybe start the next admission
            free = np.flatnonzero(self._slot_rid == -1)
            if len(free) == 0 or not self._queue:
                return emitted
            req = self._queue.popleft()
            if not self._chunk_grid_fits(len(req.prompt)):
                # odd max_seq where the padded grid would overrun the cache:
                # this request rides the bucketed whole-prompt path
                self._admit_full(req, int(free[0]), emitted)
                continue
            slot = int(free[0])
            pre = self._prefixes and self._match_prefix(req.prompt)
            if pre:
                ptoks, pcache, plogits = pre
                if len(ptoks) == len(req.prompt):
                    # the whole prompt is the stored prefix: admission
                    # completes with zero prefill work (_insert does not
                    # donate its source, so the master rows stay intact)
                    self.n_insert_dispatches += 1
                    self._cache = self._insert(self._cache, pcache, slot)
                    self._finish_admission(req, slot, plogits, emitted)
                    continue
                # suffix-only prefill: the pending cache starts as a COPY of
                # the prefix rows (the chunk program donates its cache arg —
                # the stored master must survive for the next match)
                self._slot_rid[slot] = -2
                self._pending = (
                    req, slot, jax.tree.map(jnp.copy, pcache), len(ptoks)
                )
                continue
            self._slot_rid[slot] = -2  # reserve: not free, not decoding
            self._pending = (req, slot, self._fresh_cache1(), 0)

    def _admit_paged(self) -> dict[int, list]:
        """Paged admission pass — ``_admit_chunked`` with pages: advance
        the in-flight admission by ONE chunk; otherwise reserve a page
        plan for the queue head (shared prefix pages + CoW straddle +
        fresh pages for the whole prompt-grid/decode/window footprint)
        and start it. A head that cannot reserve WAITS — retirements free
        pages, and FIFO order keeps the wait fair. Exact-prefix hits
        admit with zero prefill dispatches: the shared page-table entry
        (plus at most one CoW page copy) IS the admission."""
        from dsml_tpu.serving.paging import plan_admission

        emitted: dict[int, list] = {}
        while True:
            if self._pending is not None:
                if not self._advance_pending_paged(emitted):
                    return emitted  # long admission mid-flight: decode now
                continue
            free = np.flatnonzero(self._slot_rid == -1)
            if len(free) == 0:
                return emitted
            if self.preemption and self._preempted:
                # resumes precede fresh admissions: a preempted request
                # already paid its prefill (and its queue wait) — parking
                # it behind new work would turn one eviction into
                # unbounded starvation. A resume that cannot reserve yet
                # holds the line (FIFO; retirements free pages).
                if not self._try_resume(self._preempted[0], int(free[0])):
                    from dsml_tpu.serving.paging import note_page_wait

                    rid = self._preempted[0]["req"].rid
                    first = self._page_wait_rid_queue != rid
                    self._page_wait_rid_queue = rid
                    note_page_wait(
                        self._obs, self.obs_replica, self.obs_role,
                        trace=(self._preempted[0]["req"].trace_ctx()
                               if first else None),
                    )
                    return emitted
                self._preempted.popleft()
                continue
            if not self._queue:
                return emitted
            req = self._queue[0]  # peek: pop only once pages are reserved
            L = len(req.prompt)
            slot = int(free[0])
            pre = self._prefixes and self._match_prefix(req.prompt)
            ptoks, ppages, plogits = pre if pre else (None, None, None)
            p_len = len(ptoks) if pre else 0
            plan = plan_admission(
                self._pages, self.page_size,
                self._reserve_rows(L, req.max_new_tokens, p_len),
                prefix_pages=ppages, prefix_len=p_len,
            )
            if plan is None:
                if (self._pages.used_pages == self._registry_pages
                        and self.n_active == 0 and not self._inject):
                    # the pool is as empty as it will ever get and the
                    # head still can't reserve — a prefix registered
                    # AFTER this submit shrank the ceiling past it. Fail
                    # loudly instead of livelocking the FIFO (submit()'s
                    # never-fits check guards the normal order).
                    raise RuntimeError(
                        f"request {req.rid} can never reserve its pages "
                        f"({self._registry_pages} held by the prefix "
                        "registry); register prefixes before accepting "
                        "traffic, or raise n_pages"
                    )
                from dsml_tpu.serving.paging import note_page_wait

                first = self._page_wait_rid_queue != req.rid
                self._page_wait_rid_queue = req.rid
                note_page_wait(self._obs, self.obs_replica, self.obs_role,
                               trace=req.trace_ctx() if first else None)
                return emitted  # pool full: wait for retirements
            self._queue.popleft()
            self._assign_slot_pages(slot, plan)
            if pre and p_len == L:
                # the whole prompt is the registered prefix: admission
                # completes with zero prefill work and zero row copies
                self._finish_admission(req, slot, plogits, emitted)
                continue
            self._slot_rid[slot] = -2  # reserve: not free, not decoding
            self._pending = (req, slot, p_len, req.prompt, None)

    def _advance_pending_paged(self, emitted: dict) -> bool:
        """Run ONE chunk of the in-flight paged admission — the chunk
        writes straight into the slot's reserved pool pages (no side
        cache, no final insert dispatch). Returns True when the admission
        completed this call. ``seq`` is the row stream being prefilled —
        the prompt for a fresh admission, prompt + emitted tokens for a
        recompute RESUME (``resume`` then carries the decode state to
        restore; the final chunk's logits are discarded — the resumed
        request already sampled its next input)."""
        req, slot, start, seq, resume = self._pending
        c = self.prefill_chunk or self.page_size
        L = len(seq)
        end = min(start + c, L)
        padded = np.zeros((1, c), np.int32)
        padded[0, : end - start] = seq[start:end]
        is_last = end >= L
        last_local = (L - 1) - start if is_last else c - 1
        table_row = jnp.asarray(self._page_table[slot : slot + 1])
        logits, self._pool = self._prefill_chunk_paged(
            self.params, self._pool, table_row, jnp.asarray(padded),
            jnp.int32(start), jnp.int32(last_local),
        )
        self.n_prefill_dispatches += 1
        if not is_last:
            self._pending = (req, slot, start + c, seq, resume)
            return False
        self._pending = None
        if resume is not None:
            self._restore_slot(req, slot, resume["pos"], resume["last_tok"])
            return True
        self._finish_admission(req, slot, logits[0], emitted)
        return True

    def _finished(self, req: Request, tok: int) -> bool:
        return (self.eos_id is not None and tok == self.eos_id) or (
            len(req.tokens) >= req.max_new_tokens
        )

    def _retire(self, req: Request) -> None:
        req.done = True
        req.finished_at = time.monotonic()
        self._latency.append((
            (req.first_token_at or req.finished_at) - req.submitted_at,  # TTFT
            req.finished_at - req.submitted_at,  # e2e
        ))
        if self._obs.enabled:
            from dsml_tpu.obs import flight_recorder, get_tracer

            # per-request lifecycle in the flight ring: a serving postmortem
            # shows which requests were in flight and their tail latencies
            extra = {"trace_id": req.trace_id} if req.trace_id else {}
            flight_recorder.record(
                "serving_retire", rid=req.rid, tokens=len(req.tokens),
                e2e_ms=round((req.finished_at - req.submitted_at) * 1e3, 3),
                **extra,
            )
            ctx = req.trace_ctx()
            if ctx is not None:
                # flow END: the request's causal chain terminates on this
                # decode worker's lane (retire is the one stage that knows)
                get_tracer().flow("serving_retire", ctx, phase="end",
                                  rid=req.rid, outcome="retired",
                                  replica=self.obs_replica)
        # move out of the live table so a long-running server doesn't
        # accumulate one Request per lifetime request; collect() drains
        self._done[req.rid] = self._live.pop(req.rid)

    def _note_emissions(self, emitted: dict) -> None:
        """Record per-request inter-emission GAPS — the consumer-visible
        latency samples. A quantum/window of k tokens arrives as ONE
        emission, so a gap spans one scheduler tick; a tick stalled behind
        another request's admission shows up as a genuinely long gap (the
        head-of-line signal per-request averages would smooth away)."""
        now = time.monotonic()
        for rid, toks in emitted.items():
            if not toks:
                continue
            req = self._live.get(rid) or self._done.get(rid)
            if req is None:
                continue
            if req.last_emit_at is not None:
                self._gaps.append(now - req.last_emit_at)
            req.last_emit_at = now

    def latency_stats(self) -> dict:
        """p50/p99 TTFT, inter-emission gap, and end-to-end seconds since
        construction (or the last ``reset_latency_stats``) — the standard
        online-serving metrics; throughput alone hides queueing and
        head-of-line behavior. ``gap_*`` percentiles are over PER-EMISSION
        gap samples pooled across requests (with ``decode_quantum=k`` one
        emission carries up to k tokens — up to ``k * turbo_factor`` on a
        turbo tick — so divide by the emission's token count for a
        per-token figure)."""
        out = {"n_requests": len(self._latency)}
        if not self._latency:
            return out

        def pct(vals, q):
            return round(float(np.percentile(np.asarray(vals), q)), 6)

        ttft, e2e = zip(*self._latency)
        out.update(
            ttft_p50_s=pct(ttft, 50), ttft_p99_s=pct(ttft, 99),
            e2e_p50_s=pct(e2e, 50), e2e_p99_s=pct(e2e, 99),
        )
        if self._gaps:
            out["gap_p50_s"] = pct(self._gaps, 50)
            out["gap_p99_s"] = pct(self._gaps, 99)
        return out

    def reset_latency_stats(self) -> None:
        self._latency.clear()
        self._gaps.clear()

    def step(self) -> dict[int, list]:
        """One scheduler tick: admit, one decode QUANTUM over ALL slots,
        emit. Returns {rid: [new tokens]} for every request that produced
        tokens this tick — including each admission's prefill-sampled first
        token (a request finishing mid-quantum gets its truncated tail; the
        over-decoded lane-ticks are the quantum's scheduling cost)."""
        if not self._obs.enabled:
            emitted = self._step_inner()
            self._note_emissions(emitted)
            return emitted
        from dsml_tpu.obs import get_tracer

        # one span per scheduler tick (decode quantum + admissions): the
        # decode leg of request tracing — a request's inter-token stalls
        # land inside these spans on the worker's own timeline lane
        with get_tracer().span("decode_tick", replica=self.obs_replica,
                               n_active=self.n_active):
            emitted = self._step_inner()
        self._note_emissions(emitted)
        if self._obs.enabled:
            # batch occupancy per tick: the utilization signal behind
            # "should this deployment raise n_slots"
            self._obs.histogram(
                "serving_slot_occupancy", "active slots / n_slots per tick",
                labels=("replica", "role"),
                buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0),
            ).observe(self.n_active / self.n_slots,
                      replica=self.obs_replica, role=self.obs_role)
            self._obs.gauge(
                "serving_queue_depth", "requests waiting for a slot",
                labels=("replica", "role"),
            ).set(self.n_queued + self.n_injected,
                  replica=self.obs_replica, role=self.obs_role)
            self._obs.counter(
                "serving_tokens_total", "tokens emitted",
                labels=("replica", "role"),
            ).inc(sum(len(t) for t in emitted.values()),
                  replica=self.obs_replica, role=self.obs_role)
            # pool occupancy gauges are NOT exported here: they refresh
            # at scrape time via the collect hook registered at
            # construction (_export_pool_gauges) — a per-tick export
            # would freeze an idle batcher's pool metrics at the last
            # tick's values (the frozen-SLO-burn-gauge bug class)
        return emitted

    def _export_pool_gauges(self) -> None:
        """Collect-hook body: the (replica, role)-labeled pool
        occupancy/free-list/CoW gauges, computed from the pool's CURRENT
        state at every exposition (``Registry.add_collect_hook``) —
        /metrics between ticks shows live occupancy, and an idle
        batcher's gauges can never freeze. Reads ``obs_replica`` at call
        time, so a fleet's restamp after spawn is reflected."""
        if not self._obs.enabled:
            # collect hooks run even on a disabled registry; every set()
            # below would no-op anyway — skip the pool reads and the
            # memory_pressure() device poll outright
            return
        from dsml_tpu.serving.paging import export_pool_gauges

        export_pool_gauges(self._obs, self._pages,
                           self.obs_replica, self.obs_role)
        self._obs.gauge(
            "serving_memory_pressure",
            "device-memory pressure in [0,1]: measured bytes_in_use / "
            "bytes_limit when the backend reports memory_stats, else the "
            "pool's allocated-page fraction",
            labels=("replica", "role"),
        ).set(self.memory_pressure(), replica=self.obs_replica,
              role=self.obs_role)

    def _bytes_per_page(self) -> float:
        """PER-DEVICE bytes of ONE physical page — computed once from the
        live pool arrays via their addressable shards (so int4 rows, GQA
        head counts, and tp sharding are all reflected: a tp=2 pool's
        head-sharded arrays claim what ONE chip holds, not the global
        nbytes — never re-derived analytically)."""
        if self._page_nbytes is None:
            from dsml_tpu.obs.memory import tree_nbytes

            total = tree_nbytes(self._pool, per_device=True)
            self._page_nbytes = total / max(self.n_pages, 1)
        return self._page_nbytes

    def _ledger_page_bytes(self) -> dict:
        """Ledger source body: the pool's device bytes as a disjoint
        live/shared/free/scratch split (sums to the full pool allocation —
        the pool buffers are resident whatever the occupancy)."""
        if not self.paged or self._pool is None:
            return {}
        bpp = self._bytes_per_page()
        shared = self._pages.shared_pages
        return {
            "live": (self._pages.used_pages - shared) * bpp,
            "shared": shared * bpp,
            "free": self._pages.free_pages * bpp,
            "scratch": bpp,
        }

    def _ledger_weight_quant_bytes(self) -> dict:
        """Ledger source body: the compressed serving weights' resident
        device bytes, packed codes and scales split — the acceptance pin
        that quantized weights never ride HBM at full width (the ratio of
        the params row to this one is the codec's compression)."""
        return dict(self._wq_bytes)

    def memory_pressure(self) -> float:
        """Device-memory pressure in [0, 1] — the preemption tier's and
        the autoscaler's signal. MEASURED when the backend reports
        ``memory_stats`` (bytes_in_use / bytes_limit: the whole chip,
        params and XLA temps included — the number an eviction decision
        actually competes against), falling back to the pool's
        allocated-page fraction on statless backends (virtual-CPU tests:
        identical behavior to the page-count era)."""
        if not self.paged:
            return 0.0
        from dsml_tpu.obs.memory import get_memory_ledger

        measured = get_memory_ledger(self._obs).measure()
        if measured["available"] and measured.get("bytes_limit"):
            return min(max(
                measured["bytes_in_use"] / measured["bytes_limit"], 0.0), 1.0)
        allocatable = max(self.n_pages - 1, 1)
        return (allocatable - self._pages.free_pages) / allocatable

    def _step_inner(self) -> dict[int, list]:
        emitted: dict[int, list] = {}
        if self._inject:
            self._admit_injected(emitted)
        # handed-off and local admissions touch disjoint rids, so a plain
        # merge cannot clobber an emission list
        if self.paged:
            emitted.update(self._admit_paged())
        else:
            emitted.update(
                self._admit_chunked() if self.prefill_chunk else self._admit()
            )
        active = np.flatnonzero(self._slot_rid >= 0)
        if len(active) == 0:
            return emitted
        if self.speculative_window:
            return self._step_speculative(emitted, active)
        if self.paged and self.preemption:
            # lazy growth: every decoding slot must own pages for this
            # tick's writes; pressure evicts (the slots list shrinks)
            active = self._ensure_decode_pages(active, self.decode_quantum)
            if not active:
                return emitted
        steps_done = np.asarray(
            [len(self._live[rid].tokens) if rid >= 0 else 0 for rid in self._slot_rid],
            np.int32,
        )
        if self.paged:
            # paged decode tick: the page table rides along; writes scatter
            # into each slot's reserved pages (free slots' into scratch)
            toks, self._pool = self._decode_paged(
                self.params, self._pool, jnp.asarray(self._decode_table()),
                jnp.asarray(self._last_tok), jnp.asarray(self._pos),
                jnp.asarray(self._slot_key), jnp.asarray(steps_done),
            )
            self.n_plain_ticks += 1
            return self._apply_decoded(
                emitted, active, np.asarray(toks), self.decode_quantum
            )
        # adaptive early-exit tick: one dispatch decodes until any active
        # slot finishes (or k_max) — engaged whenever no chunked admission
        # is mid-flight (those need the plain quantum's chunk interleave).
        # A retirement ends the tick, so a queued request admits on the
        # very next tick: large k_max costs no admission latency
        if self._decode_adaptive is not None and self._pending is None:
            remaining = np.full(self.n_slots, self.model.config.max_seq, np.int32)
            for slot in active:
                req = self._live[int(self._slot_rid[slot])]
                remaining[slot] = req.max_new_tokens - len(req.tokens)
            toks, n_steps, self._cache = self._decode_adaptive(
                self.params,
                self._cache,
                jnp.asarray(self._last_tok),
                jnp.asarray(self._pos),
                jnp.asarray(self._slot_key),
                jnp.asarray(steps_done),
                jnp.asarray(remaining),
                jnp.asarray(self._slot_rid >= 0),
            )
            self.n_adaptive_ticks += 1
            quantum = int(n_steps)
            toks = np.asarray(toks)[:quantum]  # rows past the stop are zeros
            return self._apply_decoded(emitted, active, toks, quantum)
        # turbo escalation: in steady-state decode (nothing waiting to
        # admit) the escalated program amortizes the per-dispatch host round
        # trip turbo_factor x. Gate on the LARGEST remaining budget: with an
        # empty queue a slot freed mid-tick would sit idle under plain ticks
        # too, so turbo wastes nothing a plain schedule would have used — it
        # just needs one slot that consumes the whole tick to pay for it.
        # (A mid-tick EOS/budget finish retires exactly as under plain
        # ticks; the continuing-slot position invariant is budget-derived
        # and holds for any quantum.)
        quantum = self.decode_quantum
        decode = self._decode
        if (
            self._decode_turbo is not None
            and not self._queue
            and self._pending is None
        ):
            turbo_q = self.decode_quantum * self.turbo_factor
            remaining = max(
                self._live[int(self._slot_rid[s])].max_new_tokens
                - len(self._live[int(self._slot_rid[s])].tokens)
                for s in active
            )
            if remaining >= turbo_q:
                quantum, decode = turbo_q, self._decode_turbo
        if quantum == self.decode_quantum:
            self.n_plain_ticks += 1
        else:
            self.n_turbo_ticks += 1
        toks, self._cache = decode(
            self.params,
            self._cache,
            jnp.asarray(self._last_tok),
            jnp.asarray(self._pos),
            jnp.asarray(self._slot_key),
            jnp.asarray(steps_done),
        )
        toks = np.asarray(toks)  # [quantum, n_slots]
        return self._apply_decoded(emitted, active, toks, quantum)

    def _apply_decoded(self, emitted: dict, active, toks, quantum: int) -> dict:
        """Apply one tick's decoded tokens ``toks [quantum, n_slots]`` to
        the per-slot requests: emit, retire on EOS/budget (truncating a
        finished slot's tail), and advance continuing slots' positions."""
        for slot in active:
            req = self._live[int(self._slot_rid[slot])]
            new = emitted.setdefault(req.rid, [])
            for i in range(quantum):
                tok = int(toks[i, slot])
                req.tokens.append(tok)
                new.append(tok)
                if self._finished(req, tok):
                    self._retire(req)
                    self._slot_rid[slot] = -1  # freed → next admit reuses it
                    self._free_slot_pages(slot)
                    break
            if self._slot_rid[slot] >= 0:  # request continues
                self._pos[slot] += quantum
                # the jitted scan clamps its cache writes at max_seq-1; a
                # CONTINUING request must never need that clamp (submit()'s
                # L + max_new <= max_seq budget guarantees the next write
                # index is in range). Surface the invariant here rather
                # than silently diverge from the device-side positions.
                assert self._pos[slot] < self.model.config.max_seq, (
                    f"slot {slot} position {self._pos[slot]} escaped max_seq="
                    f"{self.model.config.max_seq}; host/device cache positions"
                    " have diverged"
                )
                self._last_tok[slot] = int(toks[-1, slot])
        return emitted

    def _active_accept_ewma(self) -> float | None:
        """The adaptive window's acceptance signal: the mean of ACTIVE
        slots' per-slot EWMAs — the requests actually in flight set the
        width, not a retired request's stale rate — falling back to the
        batcher-level EWMA while no active slot has a measurement yet
        (fresh admissions), and None before any measurement at all."""
        active = self._slot_accept[self._slot_rid >= 0]
        vals = active[~np.isnan(active)]
        if len(vals):
            return float(vals.mean())
        return self.accept_ewma

    def _spec_window_for_tick(self) -> int:
        """This tick's verify-window width. Fixed at ``speculative_window``
        unless ``speculative_adaptive``: then the width tracks the
        measured acceptance (:meth:`_active_accept_ewma`) — one draft
        beyond the expected accepted count, floored at 2, capped at the
        configured max — so a workload whose drafts stop landing stops
        paying for wide verify windows (each window column is verify
        FLOPs + cache-read bandwidth), and one whose drafts land climbs
        back to the full window. Greedy tokens are IDENTICAL at any width
        (each tick commits the model's own greedy chain), so adapting is
        pure scheduling — pinned in tests. Starts at the max width
        (optimistic) until the first acceptance measurement lands."""
        w_max = self.speculative_window
        if not self.speculative_adaptive:
            return w_max
        acc = self._active_accept_ewma()
        if acc is None:
            return w_max
        expected = 1.0 + acc * (w_max - 1)
        return max(2, min(w_max, int(np.ceil(expected)) + 1))

    def predicted_tpot_s(self) -> float | None:
        """Acceptance-aware per-token decode latency prediction: the
        measured verify-tick wall EWMA over the measured
        committed-tokens-per-slot-tick EWMA. None until both are warm (or
        when not speculating) — the router then falls back to its
        harvested TPOT EWMA. This is how per-slot acceptance feeds the
        SLO router's cost model: a worker whose drafts stop landing gets
        expensive BEFORE its harvested TPOT catches up."""
        if (not self.speculative_window or self.spec_tick_s_ewma is None
                or not self.commit_ewma):
            return None
        return self.spec_tick_s_ewma / max(self.commit_ewma, 1.0)

    def _step_speculative(self, emitted: dict, active) -> dict[int, list]:
        """One speculative tick: per-slot prompt-lookup drafts (host-side,
        the shared ``models.speculative`` rule), ONE verify call over all
        slots at their own depths (``verify_step`` dense /
        ``verify_step_paged`` through the page table), then per-slot
        greedy-chain acceptance — each active slot commits 1..w tokens.
        Inactive slots carry a dummy window at position 0 whose garbage
        rows land in their own dead cache rows (dense) or the scratch
        page (paged) and are never read. Acceptance-rate EWMAs update
        per slot here — the adaptive window and the router's TPOT cost
        model both feed on them."""
        w = self._spec_window_for_tick()
        if self.paged and self.preemption:
            # the verify window writes rows pos..pos+w-1 — grow first
            active = self._ensure_decode_pages(active, w)
            if len(active) == 0:
                return emitted
        toks = np.zeros((self.n_slots, w), np.int32)
        pos = np.zeros(self.n_slots, np.int32)
        for slot in active:
            req = self._live[int(self._slot_rid[slot])]
            history = np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)]
            )
            toks[slot, 0] = self._last_tok[slot]
            toks[slot, 1:] = _lookup_draft(history, self.speculative_ngram, w - 1)
            pos[slot] = self._pos[slot]
        t0 = time.monotonic()
        if self.paged:
            logits, self._pool = self._verify_paged(
                self.params, self._pool, jnp.asarray(self._decode_table()),
                jnp.asarray(toks), jnp.asarray(pos),
            )
        else:
            logits, self._cache = self._verify(
                self.params, self._cache, jnp.asarray(toks), jnp.asarray(pos)
            )
        greedy = np.asarray(jnp.argmax(logits, axis=-1))  # [n_slots, W]
        wall = time.monotonic() - t0  # greedy pull forced the dispatch
        self.n_spec_ticks += 1
        self.spec_window_used[w] = self.spec_window_used.get(w, 0) + 1
        self.spec_tick_s_ewma = (
            wall if self.spec_tick_s_ewma is None
            else 0.8 * self.spec_tick_s_ewma + 0.2 * wall
        )
        committed_total = 0
        for slot in active:
            req = self._live[int(self._slot_rid[slot])]
            new = emitted.setdefault(req.rid, [])
            drafts = toks[slot, 1:]
            committed = 0
            measured = True  # False when retirement censors the window
            for i in range(w):
                # greedy[i] is the model's next token after consuming window
                # position i — valid iff every draft before it matched the
                # chain, which is exactly how far this loop gets
                tok = int(greedy[slot, i])
                req.tokens.append(tok)
                new.append(tok)
                self._last_tok[slot] = tok
                committed += 1
                if self._finished(req, tok):
                    self._retire(req)
                    self._slot_rid[slot] = -1  # freed → next admit reuses it
                    self._free_slot_pages(slot)
                    # EOS/budget cut the window short: the unconsumed
                    # drafts were never judged, so this tick is not an
                    # acceptance sample (unless the window was already
                    # fully accepted)
                    measured = committed == w
                    break
                if i == w - 1 or int(drafts[i]) != tok:
                    break  # draft diverged (or window exhausted): stop here
            committed_total += committed
            if measured and w > 1:
                rate = (committed - 1) / (w - 1)
                prev = self._slot_accept[slot]
                self._slot_accept[slot] = (
                    rate if np.isnan(prev) else 0.8 * prev + 0.2 * rate
                )
                self.accept_ewma = (
                    rate if self.accept_ewma is None
                    else 0.8 * self.accept_ewma + 0.2 * rate
                )
            if self._slot_rid[slot] >= 0:  # request continues
                self._pos[slot] += committed
                # the next verify window writes rows pos..pos+W-1; submit()'s
                # L + max_new + W - 1 <= max_seq budget keeps it in range
                assert self._pos[slot] + w <= self.model.config.max_seq, (
                    f"slot {slot} verify window would escape max_seq="
                    f"{self.model.config.max_seq}"
                )
        mean_commit = committed_total / len(active)
        self.commit_ewma = (
            mean_commit if self.commit_ewma is None
            else 0.8 * self.commit_ewma + 0.2 * mean_commit
        )
        if self._obs.enabled and self.accept_ewma is not None:
            self._obs.gauge(
                "serving_spec_accept_rate",
                "speculative draft acceptance rate (EWMA)",
                labels=("replica", "role"),
            ).set(self.accept_ewma, replica=self.obs_replica,
                  role=self.obs_role)
        return emitted

    def abandon(self) -> list[Request]:
        """Evacuate every UNFINISHED request — queued, mid-chunked-
        admission, and mid-decode — and reset the scheduler state (the
        replica-failure path: a ``DecodeFleet`` resubmits the returned
        requests' prompts on surviving replicas; with greedy decoding the
        re-run emits identical tokens, so a replica loss costs latency,
        never tokens). Already-retired results stay collectable via
        :meth:`collect`. Cache contents become garbage that the next
        admissions fully overwrite (the same invariant a fresh batcher
        starts with)."""
        live = [self._live[rid] for rid in sorted(self._live)]
        self._queue.clear()
        self._inject.clear()  # handed-off rows die with the replica; the
        #                       router re-prefills from the prompt
        self._live.clear()
        self._pending = None
        if self.paged and self.preemption:
            # preempted requests' pages released at eviction; their host
            # swap copies die with the replica — re-prefill reproduces
            self._preempted.clear()
        self._slot_rid[:] = -1
        self._pos[:] = 0
        self._last_tok[:] = 0
        self._slot_accept[:] = np.nan
        if self.paged:
            # every slot's pages return to the pool (registered prefix
            # pages keep the registry's reference and SURVIVE — they are
            # this worker's setup state, not a request's) — the no-leak
            # invariant the chaos smoke asserts after a replica kill
            for slot in range(self.n_slots):
                self._free_slot_pages(slot)
        if self._obs.enabled:
            from dsml_tpu.obs import flight_recorder, get_tracer

            flight_recorder.record("serving_abandon", n_requests=len(live))
            tracer = get_tracer()
            for req in live:
                if req.trace_id is not None:
                    # NOT a flow end: the router requeues these under the
                    # SAME trace — the chain continues on a survivor
                    tracer.instant(
                        "serving_abandon", trace_id=req.trace_id,
                        rid=req.rid, outcome="abandoned",
                        replica=self.obs_replica,
                    )
        return live

    def collect(self) -> dict[int, list]:
        """{rid: [tokens]} for every request retired since the last collect
        (drained — repeated calls don't re-report, and the batcher holds no
        per-request state afterwards)."""
        done = {rid: req.tokens for rid, req in self._done.items()}
        self._done.clear()
        return done

    def collect_requests(self) -> dict[int, Request]:
        """Like :meth:`collect` but returns the full :class:`Request`
        objects (tokens AND timing marks) — the router's harvest path: it
        needs per-request TTFT/TPOT samples for load-aware dispatch, which
        the token-only view discards. Drained the same way."""
        done = dict(self._done)
        self._done.clear()
        return done

    def run(self, max_steps: int = 100_000) -> dict[int, list]:
        """Drain queue + slots; returns {rid: [tokens]} for every request
        retired during (or before) this call."""
        for _ in range(max_steps):
            if (not self._queue and not self._inject
                    and self.n_active == 0 and self.n_pending == 0
                    and self.n_preempted == 0):
                break
            self.step()
        else:
            raise RuntimeError(f"serving did not drain within {max_steps} steps")
        return self.collect()
