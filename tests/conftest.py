"""Test harness: force an 8-device virtual CPU mesh before jax initializes.

Mirrors the reference's "multi-node without a cluster" test pattern
(in-process servers on ephemeral ports,
``DSML/gpu_coordinator_service/gpu_coordinator_server_test.go:20-64``) —
here the multi-device substrate itself is also virtual:
``--xla_force_host_platform_device_count=8`` gives 8 CPU devices so every
mesh/collective/sharding test runs without TPU hardware.
"""

import os

# Unit tests run on a virtual 8-device CPU mesh, pinned both through the
# environment and through jax.config before any backend initializes; chip
# runs are chip_smoke.py / benchmarks/run.py / examples, not pytest.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# no persistent compile cache in the test process: the examples' ``main()``
# and ``chip_smoke.py`` place one (``utils.platform.configure_compile_cache``),
# and a compile for a described TPU (test_tpu_compile.py) cannot be read back
# without the chip. Subprocesses a test starts keep their own.
jax.config.update("jax_enable_compilation_cache", False)

import json  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture(scope="session")
def mesh8(devices8):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices8).reshape(8), ("dev",))


@pytest.fixture(scope="session")
def dp_mesh8(devices8):
    """Framework-shaped mesh (pp/dp/fsdp/sp/tp axes) with dp=8."""
    from dsml_tpu.parallel.mesh import data_mesh

    return data_mesh(devices=devices8)


# ---------------------------------------------------------------------------
# synthetic history records in the shapes the removed pre-chip harness
# left (obs.regress extraction + gate tests; ROADMAP Design 13)
# ---------------------------------------------------------------------------

_CMD = "python bench.py"  # history: the ``cmd`` those records carried; obs.regress never reads it


def _full_record(n: int, scale: float = 1.0) -> dict:
    """A driver record whose tail holds the whole JSON line and whose
    ``parsed`` payload is intact."""
    line = {
        "metric": "mnist_samples_per_sec_per_chip", "value": 1200.5 * scale,
        "unit": "samples/s/chip",
        "extras": {
            "device": "cpu0", "mnist_batch": 256,
            "allreduce_ring_p50_ms": 0.5 * scale, "allreduce_naive_p50_ms": 0.9 * scale,
            "allreduce_e2e_p50_ms": 3.0 * scale, "allreduce_payload_mb": 1.0,
            "gpt2_step_ms": 80.0 * scale,
        },
    }
    return {"n": n, "cmd": _CMD, "rc": 0,
            "tail": "some log line\n" + json.dumps(line) + "\n", "parsed": line}


# a 2000-byte-style tail cut mid-JSON on BOTH ends, parsed null
_TRUNCATED_TAIL = (
    'ignal": "none", "mnist_samples_per_sec": 1180.25, "mnist_batch": 256, '
    '"mnist_compile_s": 6.53, "gpt2_realtext_first_loss": 4.8977, '
    '"gpt2_realtext_eval_ppl": 13.72, "allreduce_ring_p50_ms": 0.52, '
    '"allreduce_naive_p50_ms": 0.95, "allreduce_e2e_p50_ms": 3.1, '
    '"allreduce_payload_mb": 1.0, "allreduce_devices": 8, "gpt2_step_ms": 81.5, "refe'
)


@pytest.fixture
def bench_history(tmp_path):
    """Synthetic BENCH_r01..r05.json in ``tmp_path``, covering the three
    record shapes a capture can take: full (r01, r02, r05), tail cut
    mid-JSON (r03), and an rc=124 timeout with no metrics (r04)."""
    records = {
        1: _full_record(1), 2: _full_record(2, 1.02),
        3: {"n": 3, "cmd": _CMD, "rc": 0, "tail": _TRUNCATED_TAIL, "parsed": None},
        4: {"n": 4, "cmd": _CMD, "rc": 124, "tail": "WARNING: a log line\n", "parsed": None},
        5: _full_record(5, 0.99),
    }
    for n, rec in records.items():
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(rec, indent=2))
    return tmp_path
