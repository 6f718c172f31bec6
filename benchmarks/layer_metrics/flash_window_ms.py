"""Device time of the flash kernels inside the program's ``attn_window`` scope:
the sliding layers' calls, forward, recomputed forward and backward (the full
layers' are ``flash_fwd_ms + flash_dkv_ms`` less this). The window skips the
tiles wholly older than it; the grid still steps over them. ms a step.
"""

from benchmarks import moe_reduce


def read(trace, notes):
    return moe_reduce.name_ms(trace, moe_reduce.FLASH_IN_WINDOW)
