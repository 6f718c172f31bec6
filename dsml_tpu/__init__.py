"""dsml_tpu — a TPU-native distributed ML training framework.

A ground-up re-design of the capabilities of
``Helenbzbz/Distributed-Machine-Learning-Pipeline`` (a gRPC-simulated
NCCL-style data-parallel pipeline, see ``SURVEY.md``) for real TPU hardware:

- ``dsml_tpu.ops``       — XLA collectives (ring all-reduce over ICI via
  ``ppermute``, dtype-aware ReduceOps), attention ops, Pallas kernels.
- ``dsml_tpu.parallel``  — device-mesh parallelism: DP, TP, PP, SP (ring
  attention), Ulysses/2D context parallelism, EP (MoE).
- ``dsml_tpu.models``    — model families (MLP, CNN, ResNet-18, GPT-2).
- ``dsml_tpu.comm``      — the reference's wire-compatible gRPC control plane
  (CommInit / Memcpy / streams / AllReduceRing / health monitoring) backed by
  real device buffers instead of simulated byte maps.
- ``dsml_tpu.runtime``   — native (C++) host runtime: buffer/address registry,
  stream engine, IDX data parsing.
- ``dsml_tpu.checkpoint`` — preemption-safe sharded checkpointing: native
  binary-piece + JSON-manifest format, async atomic commits, resumable
  data iterators (``docs/CHECKPOINT.md``).
- ``dsml_tpu.obs``       — unified observability: metrics registry
  (counters/gauges/histograms, Prometheus + JSONL exposition), span
  tracing (Chrome trace-event export), step-time breakdown and
  goodput/MFU accounting (``docs/OBSERVABILITY.md``).
- ``dsml_tpu.utils``     — config, logging, metrics, tracing, and the
  checkpoint compat front-end (``utils.checkpoint.Checkpointer``).

The package name is the importable form of the repo's
``distributed-machine-learning-pipeline_tpu`` framework ("DSML" is the
reference's own module name, ``/root/reference/DSML``).
"""

__version__ = "0.1.0"

# Lazy subpackage access keeps the heavy subpackages (models, comm, …) out
# of the import path until used.
_SUBPACKAGES = ("ops", "parallel", "models", "comm", "runtime", "utils", "cli",
                "checkpoint", "obs", "serving")


def __getattr__(name):
    if name in _SUBPACKAGES:
        import importlib

        mod = importlib.import_module(f"{__name__}.{name}")
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
