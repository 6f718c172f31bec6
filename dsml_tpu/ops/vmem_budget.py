"""VMEM budget guard for hand-scheduled Pallas kernels.

A kernel whose working set exceeds the chip's VMEM fails inside Mosaic with
an opaque allocation error at COMPILE time — long after the caller chose the
kernel path. Every hand-pipelined kernel in this tree (the double-buffered
paged-attention walk, the dequant-fused decode matmul) therefore sizes its
buffers HERE, at trace time, against the same model: blocks live in VMEM at
their Mosaic-padded footprint (last dim padded to the 128-lane width,
second-minor to the dtype's sublane tile), manual double buffering doubles
every streamed buffer, and Pallas' own automatic pipelining double-buffers
grid-walked BlockSpec operands. If the estimate doesn't fit,
:func:`require_vmem` raises a ``ValueError`` naming the geometry, the
estimate and the budget — no caller gives way to another path on its own
(docs/TUNING.md "Kernel fusion" has the sizing rule).

``DSML_VMEM_LIMIT_MB`` overrides the default 16 MiB/core budget (the v4/v5
figure the flash block sweep assumed); the guard spends at most
``_SPEND_FRACTION`` of it, leaving headroom for Mosaic's own spills,
semaphores, and the operands the estimate can't see.
"""

from __future__ import annotations

import os

__all__ = ["vmem_limit_bytes", "vmem_block_bytes", "fits_vmem", "require_vmem"]

_DEFAULT_VMEM_BYTES = 16 * 1024 * 1024  # per-core VMEM on v4/v5-class chips
_SPEND_FRACTION = 0.9  # headroom for spills/semaphores the estimate omits

# sublane tile height per itemsize (the Mosaic (sublane, 128-lane) tiling:
# f32 packs (8, 128), bf16 (16, 128), int8/uint8 (32, 128))
_SUBLANE = {4: 8, 2: 16, 1: 32}


def vmem_limit_bytes() -> int:
    """The per-core VMEM budget the guards size against. ``DSML_VMEM_LIMIT_MB``
    overrides (whole MiB; malformed/non-positive values fall back to the
    default — a bad env var must never crash a trace)."""
    raw = os.environ.get("DSML_VMEM_LIMIT_MB", "").strip()
    if raw:
        try:
            mb = int(raw)
            if mb > 0:
                return mb * 1024 * 1024
        except ValueError:
            pass
    return _DEFAULT_VMEM_BYTES


def vmem_block_bytes(shape, itemsize: int) -> int:
    """Mosaic-padded VMEM footprint of one buffer: the last dim pads to the
    128-lane width, the second-minor to the dtype's sublane tile, leading
    dims multiply through. 1-D shapes are treated as a single sublane row.
    This is why a (page, 1) f32 scale column costs a full 128-lane stripe —
    the padding is physical, so the budget must charge it."""
    dims = [int(d) for d in shape]
    if not dims:
        return itemsize
    sub = _SUBLANE.get(int(itemsize), 8)
    lanes = -(-dims[-1] // 128) * 128
    rows = -(-(dims[-2] if len(dims) >= 2 else 1) // sub) * sub
    lead = 1
    for d in dims[:-2]:
        lead *= d
    return lead * rows * lanes * itemsize


def fits_vmem(nbytes: int) -> bool:
    """True when ``nbytes`` of kernel working set fits the spendable slice
    of the VMEM budget."""
    return nbytes <= int(vmem_limit_bytes() * _SPEND_FRACTION)


def require_vmem(nbytes: int, what: str) -> None:
    """Raise unless ``nbytes`` of kernel working set fits the budget.
    ``what`` names the kernel and its geometry for the message."""
    if not fits_vmem(nbytes):
        limit = vmem_limit_bytes()
        raise ValueError(
            f"{what}: estimated VMEM working set {nbytes} B exceeds the "
            f"VMEM budget of {int(limit * _SPEND_FRACTION)} B "
            f"({_SPEND_FRACTION:.0%} of {limit} B; DSML_VMEM_LIMIT_MB "
            "overrides the limit)"
        )
