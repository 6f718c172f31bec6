"""The least time the chip could take for the attention of one step
(``flops.attention_train_flops`` over peak FLOP/s, or ``flops.attention_train_bytes`` over peak
bytes/s, whichever is larger; compute binds from 1k up) over ``flash_ms``.
"""

from benchmarks import flops


def read(trace, notes):
    if not trace or notes["peak"] is None or not trace["kind_ms_per_step"]["flash"]:
        return None
    least_s, _bound = flops.roofline_seconds(notes["attention_flops_per_step"] / notes["chips"],
                                             notes["attention_bytes_per_step"] / notes["chips"],
                                             notes["peak"])
    return 100.0 * least_s * 1e3 / trace["kind_ms_per_step"]["flash"]
