"""KV-cache handoff: moving a prefilled request to a decode worker.

The disaggregated fleet's one new data-plane object: a :class:`Handoff`
carries everything a decode worker needs to continue a request whose
prefill ran elsewhere — the prompt's 1-row KV cache, the next-token
logits at the last prompt position, and the request's identity/timing.
Three transports, cheapest first:

- **In-process** (workers share a host): the ``Handoff`` object itself is
  the transfer — the decode worker's insert DONATES the cache buffers
  (``ContinuousBatcher.inject``), so the rows move by ownership, not copy.
- **CRC-framed byte codec** (:func:`encode_handoff`/:func:`decode_handoff`):
  the cache leaves and logits serialize into one contiguous payload framed
  exactly like the migration stream path — ``MIGRATE_CHUNK``-sized frames,
  CRC32C per frame (``comm.migration.payload_chunk_crcs``) — so "one
  corrupt chunk" maps to one failed frame and a mismatch aborts the
  handoff (:class:`HandoffIntegrityError`) before any byte reaches a
  cache. :func:`frame_transport` round-trips a handoff through this codec
  with validation on — the in-process stand-in for a wire hop that tests
  use to pin bit-identity THROUGH the framing.
- **Hardened P2P streams** (:func:`register_with_donor` /
  :func:`fetch_from_migrator`): cross-host handoff rides the SAME
  machinery as elastic shard migration — the prefill host registers the
  handoff's arrays with its device server's ``StateDonor``; the decode
  host pulls them with a ``ShardMigrator`` (``BeginSend``/``StreamSend``
  under per-frame CRC32C, resumable offsets, bounded-backoff retries,
  donor-death fallback). A failed fetch raises ``MigrationError`` and the
  router re-prefills on a survivor — the handoff is always reproducible
  from the prompt, so stream loss costs latency, never tokens.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "HANDOFF_SCHEMA",
    "Handoff",
    "HandoffIntegrityError",
    "decode_handoff",
    "encode_handoff",
    "fetch_from_migrator",
    "frame_transport",
    "register_with_donor",
]

HANDOFF_SCHEMA = "dsml.serving.handoff/1"


class HandoffIntegrityError(RuntimeError):
    """The handoff payload failed CRC32C frame validation (or its sizes
    disagree with the header). The contract mirrors the migration path's:
    corrupted rows NEVER land in a decode cache — the caller re-fetches or
    re-prefills from the prompt (which reproduces identical rows)."""


@dataclasses.dataclass
class Handoff:
    """One prefilled request in flight between worker roles.

    ``cache1`` is the per-layer 1-row KV cache (``model.init_cache(1)``
    layout — plain k/v or quantized k/k_s/v/v_s entries ride the same
    field), filled for positions ``[0, prefill_len)``. ``logits`` is the
    last prompt position's next-token row; the decode worker samples the
    first token from it under the (seed, ``key_rid``, step) fold.
    ``submitted_at``/``prefill_done_at`` are ``time.monotonic`` marks the
    router uses for true end-to-end TTFT and for splitting prefill wait
    from decode wait in its load estimates."""

    frid: int
    prompt: np.ndarray          # [L] int32
    max_new_tokens: int
    prefill_len: int
    cache1: list                # per-layer {entry: array [1, H, max_seq, ·]}
    logits: np.ndarray          # [vocab]
    submitted_at: float = 0.0
    prefill_done_at: float = 0.0
    key_rid: int | None = None
    # PAGED handoff (docs/SERVING.md § Paged KV): when ``page_size`` is
    # set, ``cache1`` holds the shipped PAGES instead — per-layer dicts
    # with a leading shipped-page axis [n_ship, H, page_size, ·] in the
    # decode pool's own (quantized) entry layout, so the wire carries
    # int4 pages (~8x fewer bytes than dense f32 rows) and the decode
    # worker installs them verbatim. ``prefix_rows`` leading rows are NOT
    # shipped: the decode worker shares its own registered prefix pages
    # for them (the fleet-level CoW elision; always a page multiple).
    page_size: int | None = None
    prefix_rows: int = 0
    # request-scoped trace identity (obs.TraceContext): minted at
    # Router.submit, stamped by the prefill worker, carried through BOTH
    # wire paths (codec header + donor descriptor header) so the decode
    # host's spans join the same causal chain. ``parent_span`` names the
    # emitting stage's span — the cross-process parent link.
    trace_id: str | None = None
    parent_span: str = ""


def _leaves(cache1) -> list:
    """Deterministic leaf order — (layer index, sorted entry keys) — so
    encoder and decoder (and the donor/migrator key scheme) agree on the
    payload layout without any negotiation."""
    out = []
    for i, layer in enumerate(cache1):
        for key in sorted(layer):
            out.append((i, key, layer[key]))
    return out


def _host(arr) -> np.ndarray:
    # device arrays pull to host once here; numpy passes through
    return np.ascontiguousarray(np.asarray(arr))


def encode_handoff(handoff: Handoff) -> dict:
    """Serialize a handoff into ``{"header", "payload", "chunk_crcs"}``:
    one contiguous byte payload (cache leaves in :func:`_leaves` order,
    logits last) plus the CRC32C frame table at ``MIGRATE_CHUNK``
    granularity. The header is JSON-able — a wire implementation ships it
    over its control channel and the payload over the data plane."""
    # imported here, not at module top: the comm stack (grpc) must not
    # ride along with `from dsml_tpu.serving import ContinuousBatcher`
    from dsml_tpu.comm.migration import payload_chunk_crcs

    parts, leaves = [], []
    for i, key, arr in _leaves(handoff.cache1):
        a = _host(arr)
        parts.append(a.tobytes())
        leaves.append({
            "layer": i, "entry": key, "dtype": str(a.dtype),
            "shape": list(a.shape), "nbytes": len(parts[-1]),
        })
    logits = _host(handoff.logits).astype(np.float32, copy=False)
    parts.append(logits.tobytes())
    payload = b"".join(parts)
    header = {
        "schema": HANDOFF_SCHEMA,
        "frid": int(handoff.frid),
        "key_rid": None if handoff.key_rid is None else int(handoff.key_rid),
        "prompt": [int(t) for t in handoff.prompt],
        "max_new_tokens": int(handoff.max_new_tokens),
        "prefill_len": int(handoff.prefill_len),
        "submitted_at": float(handoff.submitted_at),
        "prefill_done_at": float(handoff.prefill_done_at),
        "n_layers": len(handoff.cache1),
        "page_size": handoff.page_size,
        "prefix_rows": int(handoff.prefix_rows),
        "trace_id": handoff.trace_id,
        "parent_span": handoff.parent_span,
        "leaves": leaves,
        "logits_nbytes": len(parts[-1]),
        "total_nbytes": len(payload),
    }
    return {"header": header, "payload": payload,
            "chunk_crcs": payload_chunk_crcs(payload)}


def decode_handoff(frame: dict, validate: bool = True) -> Handoff:
    """Reconstruct a :class:`Handoff` from :func:`encode_handoff` output,
    validating every CRC32C frame first (``validate=False`` skips only the
    CRC pass — sizes are always checked). Cache leaves come back as host
    numpy; ``ContinuousBatcher.inject`` re-places them on device."""
    from dsml_tpu.comm.migration import payload_chunk_crcs

    header, payload = frame["header"], frame["payload"]
    if header.get("schema") != HANDOFF_SCHEMA:
        raise HandoffIntegrityError(
            f"unknown handoff schema {header.get('schema')!r}"
        )
    if len(payload) != int(header["total_nbytes"]):
        raise HandoffIntegrityError(
            f"payload is {len(payload)} bytes, header says "
            f"{header['total_nbytes']}"
        )
    if validate:
        got = payload_chunk_crcs(payload)
        want = list(frame["chunk_crcs"])
        bad = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
        if len(got) != len(want) or bad:
            raise HandoffIntegrityError(
                f"CRC32C mismatch on handoff frid={header['frid']}: "
                f"frame(s) {bad[:8]} of {len(got)} failed validation"
            )
    cache1: list = [{} for _ in range(int(header["n_layers"]))]
    off = 0
    for leaf in header["leaves"]:
        n = int(leaf["nbytes"])
        arr = np.frombuffer(
            payload[off : off + n], dtype=np.dtype(leaf["dtype"])
        ).reshape(leaf["shape"])
        cache1[int(leaf["layer"])][leaf["entry"]] = arr
        off += n
    logits = np.frombuffer(
        payload[off : off + int(header["logits_nbytes"])], dtype=np.float32
    )
    return Handoff(
        frid=int(header["frid"]),
        prompt=np.asarray(header["prompt"], np.int32),
        max_new_tokens=int(header["max_new_tokens"]),
        prefill_len=int(header["prefill_len"]),
        cache1=cache1,
        logits=logits,
        submitted_at=float(header["submitted_at"]),
        prefill_done_at=float(header["prefill_done_at"]),
        key_rid=header.get("key_rid"),
        page_size=header.get("page_size"),
        prefix_rows=int(header.get("prefix_rows", 0)),
        trace_id=header.get("trace_id"),
        parent_span=str(header.get("parent_span") or ""),
    )


def frame_transport(handoff: Handoff) -> Handoff:
    """Round-trip a handoff through the CRC-framed codec with validation —
    the transport the router uses to prove (and tests to pin) that the
    wire framing itself never perturbs tokens. A real deployment replaces
    this hop with the donor/migrator stream pull below."""
    return decode_handoff(encode_handoff(handoff))


# ---------------------------------------------------------------------------
# cross-host: the hardened StateDonor / ShardMigrator stream path
# ---------------------------------------------------------------------------


def register_with_donor(donor, handoff: Handoff, prefix: str | None = None) -> dict:
    """Publish a handoff on the prefill host's device server: every cache
    leaf (and the logits row) registers with the server's ``StateDonor``
    under ``<prefix>/<layer>/<entry>``, and the returned DESCRIPTOR — the
    codec header plus the key prefix, no payload — travels to the decode
    host over any control channel. The payload bytes then move via
    ``BeginSend``/``StreamSend`` when the decode host pulls
    (:func:`fetch_from_migrator`). Call ``donor.unregister(prefix)`` once
    the pull completes — handoffs are per-request transients and must not
    grow the donor table."""
    prefix = prefix if prefix is not None else f"handoff/{int(handoff.frid)}"
    # the header is built directly from the leaf metadata — the stream
    # path never needs the codec's contiguous payload (the donor frames +
    # CRCs each leaf itself at BeginSend), so serializing it here would be
    # a wasted full-cache copy + CRC pass per handoff
    leaves, total = [], 0
    for i, key, arr in _leaves(handoff.cache1):
        a = _host(arr)
        donor.register_array(f"{prefix}/{i}/{key}", a,
                             trace_id=handoff.trace_id)
        leaves.append({
            "layer": i, "entry": key, "dtype": str(a.dtype),
            "shape": list(a.shape), "nbytes": int(a.nbytes),
        })
        total += int(a.nbytes)
    logits = _host(handoff.logits).astype(np.float32, copy=False)
    donor.register_array(f"{prefix}/logits", logits,
                         trace_id=handoff.trace_id)
    header = {
        "schema": HANDOFF_SCHEMA,
        "frid": int(handoff.frid),
        "key_rid": None if handoff.key_rid is None else int(handoff.key_rid),
        "prompt": [int(t) for t in handoff.prompt],
        "max_new_tokens": int(handoff.max_new_tokens),
        "prefill_len": int(handoff.prefill_len),
        "submitted_at": float(handoff.submitted_at),
        "prefill_done_at": float(handoff.prefill_done_at),
        "n_layers": len(handoff.cache1),
        "page_size": handoff.page_size,
        "prefix_rows": int(handoff.prefix_rows),
        "trace_id": handoff.trace_id,
        "parent_span": handoff.parent_span,
        "leaves": leaves,
        "logits_nbytes": int(logits.nbytes),
        "total_nbytes": total + int(logits.nbytes),
    }
    return {"prefix": prefix, "header": header}


def fetch_from_migrator(migrator, descriptor: dict) -> Handoff:
    """Pull a published handoff over the hardened P2P streams: one
    ``ShardMigrator.fetch_piece`` per leaf (whole-array pieces), each
    delivery CRC32C-validated frame-by-frame with resumable offsets and
    donor-death retries — the exact machinery elastic shard migration
    rides. Raises ``comm.migration.MigrationError`` when a leaf cannot be
    delivered; the router's contract is then re-prefill on a survivor."""
    from dsml_tpu.obs import TraceContext, get_tracer

    header = descriptor["header"]
    prefix = descriptor["prefix"]
    ctx = TraceContext.from_header(header)
    cache1: list = [{} for _ in range(int(header["n_layers"]))]
    # the pull is the cross-host hop — a trace-tagged span (+ flow step)
    # on the DECODE host's timeline, so the stitched view shows the wire
    # time between the prefill host's handoff span and decode admission
    with get_tracer().request_span(
        "handoff_pull", ctx, flow="step", frid=int(header["frid"]),
        nbytes=int(header["total_nbytes"]),
    ):
        for leaf in header["leaves"]:
            piece = [[0, int(s)] for s in leaf["shape"]]
            arr = migrator.fetch_piece(
                f"{prefix}/{leaf['layer']}/{leaf['entry']}", piece,
                leaf["dtype"], trace_id=header.get("trace_id"),
            )
            cache1[int(leaf["layer"])][leaf["entry"]] = arr
        vocab = int(header["logits_nbytes"]) // np.dtype(np.float32).itemsize
        logits = migrator.fetch_piece(
            f"{prefix}/logits", [[0, vocab]], "float32",
            trace_id=header.get("trace_id"),
        ).reshape(-1)
    return Handoff(
        frid=int(header["frid"]),
        prompt=np.asarray(header["prompt"], np.int32),
        max_new_tokens=int(header["max_new_tokens"]),
        prefill_len=int(header["prefill_len"]),
        cache1=cache1,
        logits=logits,
        submitted_at=float(header["submitted_at"]),
        prefill_done_at=float(header["prefill_done_at"]),
        key_rid=header.get("key_rid"),
        page_size=header.get("page_size"),
        prefix_rows=int(header.get("prefix_rows", 0)),
        trace_id=header.get("trace_id"),
        parent_span=str(header.get("parent_span") or ""),
    )
