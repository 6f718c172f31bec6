"""Operations and bytes from shapes: the benchmark's own arithmetic.

``train_flops`` is a copy of ``dsml_tpu/models/common.py::
transformer_train_flops`` (PaLM-appendix accounting: forward matmuls plus the
causal attention term, backward = 2 x forward, recomputation not counted), kept
here so that a later PR can change the program's copy and not the yardstick.
``benchmarks/tests`` holds the two equal.
"""

from __future__ import annotations


def train_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """Model FLOPs of one training step over ``n_tokens`` tokens in sequences
    of ``seq``, for a dense GPT-2 block (two MLP matmuls, full-width k and v)."""
    t, d, ff = int(n_tokens), shape["d_model"], shape["d_ff"]
    fwd = shape["n_layer"] * (
        2 * t * d * d              # q projection
        + 2 * 2 * t * d * d        # k and v projections
        + 2 * t * d * d            # attention output projection
        + 2 * 2 * t * seq * d // 2  # q.k^T and p.v, causal halves the area
        + 2 * 2 * t * d * ff       # MLP in and out
    ) + 2 * t * d * shape["vocab_size"]  # output head
    return 3 * fwd


def attention_train_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """The attention term of ``train_flops`` alone: what causal attention
    needs in a training step (q.k^T and p.v forward; dv, dp, dq, dk backward),
    six half-area matmuls a layer. A flash backward also recomputes q.k^T,
    once or twice; recomputation is the kernel's cost, not the model's."""
    return 3 * shape["n_layer"] * (2 * 2 * int(n_tokens) * seq * shape["d_model"] // 2)


def attention_train_bytes(shape: dict, n_tokens: int, bytes_per_value: int = 2) -> int:
    """The least HBM traffic of attention in a training step: forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv:
    twelve ``[tokens, d_model]`` arrays a layer (the per-row log-sum-exp is
    1/head_dim of one of them and left out)."""
    return shape["n_layer"] * 12 * int(n_tokens) * shape["d_model"] * bytes_per_value


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time one chip could take, and which bound binds."""
    by_compute = flops / peak["bf16_flops_per_s"]
    by_memory = nbytes / peak["hbm_bytes_per_s"]
    return (by_compute, "compute") if by_compute >= by_memory else (by_memory, "memory")
