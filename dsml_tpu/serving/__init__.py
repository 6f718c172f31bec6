"""Serving subsystem: continuous batching, disaggregated prefill/decode.

Grown from the single-module continuous batcher (``serving.py``, now
:mod:`dsml_tpu.serving.batcher` — every historical import keeps working)
into the fleet shape production traffic wants (docs/SERVING.md):

- :mod:`batcher`  — ``ContinuousBatcher``: slot-based continuous batching
  on one replica (chunked prefill, prefix cache, turbo/adaptive quanta,
  speculative windows), now also the DECODE-worker role: ``inject()``
  admits a request whose KV rows + first logits were prefilled elsewhere.
- :mod:`prefill`  — ``PrefillWorker``: chunked prefill to completion with
  a replicated prefix registry, producing ``Handoff`` objects.
- :mod:`handoff`  — the KV-cache handoff: in-process object handover on a
  shared host, CRC32C-framed byte codec (the ``comm/migration.py``
  framing) and ``StateDonor``/``ShardMigrator`` integration for the
  cross-host stream path.
- :mod:`router`   — ``Router``: SLO-class admission with explicit
  shedding, load-aware dispatch over N prefill + M decode workers using
  queue depth and an acceptance-aware TPOT cost model, prefix
  replication, chaos hooks.
- :mod:`paging`   — the paged-KV host side: refcounting page-pool
  allocator + copy-on-write admission planning shared by the batcher
  (decode role) and the prefill worker (docs/SERVING.md § Paged KV).

The interference problem this removes: one batcher interleaves prefill
chunks with decode quanta, so a burst of long prompts inflates every
in-flight request's per-token latency. Splitting the roles keeps decode
ticks pure decode — the burst lands on the prefill pool (the
Gemma-on-TPU disaggregation result; the isolation A/B at equal chip
count is not measured on a chip).
"""

from dsml_tpu.serving.batcher import ContinuousBatcher, QueueFull, Request

# Fleet-layer exports resolve lazily (PEP 562, the dsml_tpu/__init__
# pattern): `from dsml_tpu.serving import ContinuousBatcher` — every
# historical import — must not drag the fleet modules (and through them
# the comm/grpc stack) into the process.
_LAZY = {
    "Handoff": "handoff",
    "HandoffIntegrityError": "handoff",
    "decode_handoff": "handoff",
    "encode_handoff": "handoff",
    "fetch_from_migrator": "handoff",
    "frame_transport": "handoff",
    "register_with_donor": "handoff",
    "PrefillWorker": "prefill",
    "Router": "router",
    "SLOClass": "router",
    "build_fleet": "router",
}

__all__ = ["ContinuousBatcher", "QueueFull", "Request", *sorted(_LAZY)]


def __getattr__(name):
    module = _LAZY.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    value = getattr(
        importlib.import_module(f"{__name__}.{module}"), name
    )
    globals()[name] = value
    return value
