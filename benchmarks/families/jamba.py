"""The Jamba family: from a configuration file (the source's own keys) to the
program's model, to the plain reference, and to the family's own counts of
operations and bytes (``benchmarks/flops.py`` counts a dense GPT-2 block:
attention in every layer, two MLP matmuls, full-width k and v).

The program's ``Jamba.init`` already draws on the device from the seed, so the
benchmark adds no initialiser of its own.
"""

from __future__ import annotations

from benchmarks.reference import jamba as reference
from dsml_tpu.models.jamba import Jamba, JambaConfig

DTYPE = "bfloat16"


def shape(config: dict, rehearse: bool = False) -> dict:
    """The sizes the arithmetic needs, under the program's names: the keys
    ``drivers/train.py`` and ``flops.py`` read, then the family's own.
    ``rehearse`` swaps in ``JambaConfig.tiny()``'s sizes: a CPU rehearsal of
    the control flow, never a measurement."""
    if rehearse:
        tiny = JambaConfig.tiny()
        return {k: getattr(tiny, k) for k in _KEYS}
    if config["hidden_act"] != "silu" or config["num_experts"] != 1 or not config["tie_word_embeddings"]:
        raise ValueError("the Jamba family computes a tied head and one gated SiLU MLP a layer")
    if not config["mamba_conv_bias"] or config["mamba_proj_bias"] or config["sliding_window"]:
        raise ValueError("the Jamba family computes a biased convolution, bias-free projections "
                         "and full causal attention")
    return {
        "vocab_size": config["vocab_size"],
        "max_seq": config["max_position_embeddings"],
        "n_layer": config["num_hidden_layers"],
        "n_head": config["num_attention_heads"],
        "d_model": config["hidden_size"],
        "d_ff": config["intermediate_size"],
        "n_kv_head": config["num_key_value_heads"],
        "d_inner": config["mamba_expand"] * config["hidden_size"],
        "d_state": config["mamba_d_state"],
        "dt_rank": config["mamba_dt_rank"],
        "d_conv": config["mamba_d_conv"],
        "attn_layer_period": config["attn_layer_period"],
        "attn_layer_offset": config["attn_layer_offset"],
        "rms_eps": config["rms_norm_eps"],
    }


_KEYS = ("vocab_size", "max_seq", "n_layer", "n_head", "d_model", "d_ff", "n_kv_head", "d_inner",
         "d_state", "dt_rank", "d_conv", "attn_layer_period", "attn_layer_offset", "rms_eps")


def program_model(config: dict, rehearse: bool = False) -> Jamba:
    return Jamba(JambaConfig(dtype=DTYPE, remat=config["assumed"]["remat"].startswith("whole block"),
                             **shape(config, rehearse)))


def reference_loss(config: dict, params, tokens, targets, rehearse: bool = False) -> float:
    """Mean next-token loss of the plain float32 reference on the program's
    parameter tree (one layer cast up at a time), rows one at a time."""
    sizes = shape(config, rehearse)
    return reference.loss(params, tokens, targets, n_head=sizes["n_head"],
                          n_kv_head=sizes["n_kv_head"], eps=sizes["rms_eps"])


def watched_layers(params) -> tuple[int, ...]:
    """The lowest layer of each kind. Every kernel call of the step's backward
    lies in one of them or above it, so a fault in any reaches their gradients
    through the cotangent of the residual stream."""
    first: dict = {}
    for i, layer in enumerate(params["layers"]):
        first.setdefault("ssm" in layer, i)
    return tuple(sorted(first.values()))


def reference_layer_grads(config: dict, params, tokens, targets, rehearse: bool = False,
                          precision: str = "float32") -> dict:
    """``{i: float32 gradient tree of params["layers"][i]}`` of the plain
    reference's mean loss, for the watched layers."""
    sizes = shape(config, rehearse)
    return reference.layer_grads(params, tokens, targets, watched_layers(params), n_head=sizes["n_head"],
                                 n_kv_head=sizes["n_kv_head"], eps=sizes["rms_eps"], precision=precision)


# -- the family's own counts ---------------------------------------------------

def _layers(shape: dict) -> tuple[int, int]:
    """(Mamba layers, attention layers) of the depth held here."""
    attention = sum(i % shape["attn_layer_period"] == shape["attn_layer_offset"]
                    for i in range(shape["n_layer"]))
    return shape["n_layer"] - attention, attention


def parameter_count(shape: dict) -> int:
    d, ff, e, n, r = shape["d_model"], shape["d_ff"], shape["d_inner"], shape["d_state"], shape["dt_rank"]
    kv = shape["n_kv_head"] * d // shape["n_head"]
    mamba = (d * 2 * e + (shape["d_conv"] + 1) * e + e * (r + 2 * n) + (r + 2 * n)
             + r * e + e + e * n + e + e * d)
    attention = 2 * d * d + 2 * d * kv
    layer = 3 * d * ff + 2 * d  # the gated MLP and the two norms
    n_mamba, n_attention = _layers(shape)
    return (n_mamba * (mamba + layer) + n_attention * (attention + layer)
            + shape["vocab_size"] * d + d)


def forward_flops_per_token(shape: dict, seq: int) -> int:
    """Matmuls and attention only; elementwise work (the convolution, the scan,
    norms, gates) and recomputation are not counted, as in ``flops.py``."""
    d, ff, e, n, r = shape["d_model"], shape["d_ff"], shape["d_inner"], shape["d_state"], shape["dt_rank"]
    kv = shape["n_kv_head"] * d // shape["n_head"]
    mlp = 2 * 3 * d * ff
    mamba = 2 * (d * 2 * e + e * (r + 2 * n) + r * e + e * d) + mlp
    attention = 2 * (2 * d * d + 2 * d * kv) + _attention_flops_per_token(shape, seq) + mlp
    n_mamba, n_attention = _layers(shape)
    return n_mamba * mamba + n_attention * attention + 2 * d * shape["vocab_size"]


def _attention_flops_per_token(shape: dict, seq: int) -> int:
    return 2 * 2 * seq * shape["d_model"] // 2  # q.k^T and p.v over every query head, causal halves the area


def train_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """Model FLOPs of one training step: backward = 2 x forward."""
    return 3 * int(n_tokens) * forward_flops_per_token(shape, seq)


def attention_train_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """The attention term alone, over the attention layers there are."""
    return 3 * _layers(shape)[1] * int(n_tokens) * _attention_flops_per_token(shape, seq)


def attention_train_bytes(shape: dict, n_tokens: int, bytes_per_value: int = 2) -> int:
    """The least HBM traffic of attention in a training step: q, o (forward)
    and q, o, do, dq (backward) are ``d_model`` wide; k, v (forward) and k, v,
    dk, dv (backward) as wide as the key-value heads there are."""
    kv = shape["n_kv_head"] * shape["d_model"] // shape["n_head"]
    return _layers(shape)[1] * 6 * int(n_tokens) * (shape["d_model"] + kv) * bytes_per_value


def scan_train_bytes(shape: dict, n_tokens: int, bytes_per_value: int = 2) -> int:
    """The least HBM traffic of the selective scan in a training step: the
    forward reads u, delta and writes y; the backward reads u, delta, dy and
    writes du, ddelta: eight ``[tokens, d_inner]`` arrays a Mamba layer. B, C,
    their gradients (``d_state`` wide), A, D and the block-boundary states are
    under 2% of that and left out; the recomputed forward is the program's
    cost, not the algorithm's."""
    return _layers(shape)[0] * 8 * int(n_tokens) * shape["d_inner"] * bytes_per_value
