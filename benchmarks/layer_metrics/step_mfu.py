"""Model FLOP/s utilization: the benchmark's count of the operations forward and backward need
for the tokens completed (``flops.train_flops``, recomputation not counted) over chips times the
peak.
"""


def read(trace, notes):
    if notes["peak"] is None:
        return None
    per_chip = notes["flops_per_step"] / notes["tokens_per_step"] * notes["tok_s_chip"]
    return 100.0 * per_chip / notes["peak"]["bf16_flops_per_s"]
