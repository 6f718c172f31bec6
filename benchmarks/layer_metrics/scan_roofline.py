"""The least time the chip could take for the selective scans of one step over
``scan_ms``. The scan has no MXU work and ``peaks.json`` has no VPU peak, so
the least time is the memory bound alone: the family's ``scan_train_bytes``
(eight ``[tokens, d_inner]`` arrays a Mamba layer, forward and backward, nothing
recomputed) over the chip's peak HBM bytes/s. A low share with the VPU busy says
that the kernels are bound by their arithmetic and its latency, not by HBM.
"""

from benchmarks import ssm_reduce


def read(trace, notes):
    scan_ms = ssm_reduce.name_ms(trace, *ssm_reduce.KERNELS)
    if not scan_ms or notes["peak"] is None or "scan_bytes_per_step" not in notes:
        return None
    least_s = notes["scan_bytes_per_step"] / notes["chips"] / notes["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s * 1e3 / scan_ms
