"""The least time the chip could take for the attention of one step at the
family's widths (``attention_flops_per_step`` / ``attention_bytes_per_step`` of
the run's notes: q and k at 192, v at 128, over the live causal pairs;
``flops.roofline_seconds``) over the flash kernels' own time, read by their
names (``flash_fwd`` + ``flash_dkv``, every call of a step, the recomputed
forward among them). Unlike ``flash_roofline`` no other Mosaic kernel (the
grouped matmuls) is in the denominator. ``None`` without a device trace or
where neither kernel name is found.
"""

from benchmarks import flops, scope_reduce


def read(trace, notes):
    if not trace or notes["peak"] is None:
        return None
    kernels = [scope_reduce.scope_ms(trace, name) for name in ("flash_fwd", "flash_dkv")]
    if not any(kernels):
        return None
    least_s, _bound = flops.roofline_seconds(notes["attention_flops_per_step"] / notes["chips"],
                                             notes["attention_bytes_per_step"] / notes["chips"], notes["peak"])
    return 100.0 * least_s * 1e3 / sum(ms or 0.0 for ms in kernels)
