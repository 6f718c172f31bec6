"""The least time the chip could take for the experts' matmuls of one step over
``expert_ms``: the family's ``expert_train_flops`` over peak FLOP/s or its
``expert_train_bytes`` over peak bytes/s, whichever is larger (compute binds at
1,024 rows an expert). The count is the algorithm's: forward and backward of
``tokens x top_k`` pairs, nothing recomputed and no row padded, so under
whole-block remat (a fourth of the kernels' work is the forward run again) and
with each expert's rows padded to whole tiles the share cannot pass about 65%.
"""

from benchmarks import flops, moe_reduce


def read(trace, notes):
    expert_ms = moe_reduce.name_ms(trace, *moe_reduce.KERNELS)
    if not expert_ms or notes["peak"] is None or "expert_flops_per_step" not in notes:
        return None
    least_s, _bound = flops.roofline_seconds(notes["expert_flops_per_step"] / notes["chips"],
                                             notes["expert_bytes_per_step"] / notes["chips"], notes["peak"])
    return 100.0 * least_s * 1e3 / expert_ms
