"""TPU compute ops: XLA collectives, attention, and Pallas kernels."""

from dsml_tpu.ops.collectives import (  # noqa: F401
    ReduceOp,
    all_gather,
    all_reduce,
    all_to_all,
    naive_all_reduce,
    reduce_scatter,
    ring2_all_reduce,
    ring_all_reduce,
    ring_pass,
    ring_perm_tables,
)
from dsml_tpu.ops.flash import (  # noqa: F401
    flash_attention,
    flash_attention_lse,
    flash_block_grads,
    ring_flash_attention,
)
from dsml_tpu.ops.grouped_matmul import grouped_matmul  # noqa: F401
from dsml_tpu.ops.ring_attention import (  # noqa: F401
    causal_keep_fraction,
    ring_kv_wire_bytes,
)
from dsml_tpu.ops.selective_scan import selective_scan  # noqa: F401
