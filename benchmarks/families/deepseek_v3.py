"""The DeepSeek-V3 family: from a configuration file (the source's own keys) to
the program's model, to the plain reference, and to the family's own counts of
operations and bytes (``benchmarks/flops.py`` counts a dense GPT-2 block).

The program's ``DeepseekV3.init`` draws on the device from the seed, so the
benchmark adds no initialiser of its own.
"""

from __future__ import annotations

from benchmarks.reference import deepseek_v3 as reference
from dsml_tpu.models.deepseek_v3 import DeepseekV3, DeepseekV3Config

DTYPE = "bfloat16"


def shape(config: dict, rehearse: bool = False) -> dict:
    """The sizes the arithmetic needs, under the program's names: the keys
    ``drivers/train.py`` and ``flops.py`` read, then the family's own.
    ``rehearse`` swaps in ``DeepseekV3Config.tiny()``'s sizes: a CPU rehearsal
    of the control flow, never a measurement."""
    if rehearse:
        tiny = DeepseekV3Config.tiny()
        return {k: getattr(tiny, k) for k in _KEYS}
    if config["hidden_act"] != "silu" or config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("the DeepSeek-V3 family computes gated SiLU experts, no bias and an untied head")
    if (config["q_lora_rank"] is not None or config["rope_scaling"] is not None or not config["rope_interleave"]
            or config["scoring_func"] != "sigmoid" or config["topk_method"] != "noaux_tc"
            or not config["norm_topk_prob"] or config["moe_layer_freq"] != 1):
        raise ValueError("the DeepSeek-V3 family computes latent attention without a query latent, an interleaved "
                         "rotation without scaling, and a sigmoid router with a selection bias in every layer "
                         "from first_k_dense_replace on")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("the DeepSeek-V3 family builds no group limit (n_group = topk_group = 1 selects every expert)")
    held = config["experts_held"]
    if held["count"] != config["n_routed_experts"]:
        raise ValueError(f"n_routed_experts {config['n_routed_experts']} is the count of experts held, "
                         f"experts_held says {held['count']}")
    return {
        "vocab_size": config["vocab_size"],
        "max_seq": config["max_position_embeddings"],
        "n_layer": config["num_hidden_layers"],
        "n_head": config["num_attention_heads"],
        "d_model": config["hidden_size"],
        "qk_nope_dim": config["qk_nope_head_dim"],
        "qk_rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "kv_lora_rank": config["kv_lora_rank"],
        "dense_d_ff": config["intermediate_size"],
        "d_ff": config["moe_intermediate_size"],
        "n_experts": held["of"],
        "expert_top_k": config["num_experts_per_tok"],
        "n_shared_experts": config["n_shared_experts"],
        "routed_scaling": config["routed_scaling_factor"],
        "first_dense": config["first_k_dense_replace"],
        "experts_held": (held["first"], held["count"]) if held["count"] != held["of"] else None,
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": config["rms_norm_eps"],
    }


_KEYS = ("vocab_size", "max_seq", "n_layer", "n_head", "d_model", "qk_nope_dim", "qk_rope_dim", "v_head_dim",
         "kv_lora_rank", "dense_d_ff", "d_ff", "n_experts", "expert_top_k", "n_shared_experts", "routed_scaling",
         "first_dense", "experts_held", "rope_theta", "rms_eps")


def program_model(config: dict, rehearse: bool = False) -> DeepseekV3:
    assumed = config["assumed"]
    tile = DeepseekV3Config.tiny().expert_tile if rehearse else int(assumed["expert_tile"].split()[0])
    return DeepseekV3(DeepseekV3Config(dtype=DTYPE, remat=assumed["remat"].startswith("whole block"), expert_tile=tile,
                                       **shape(config, rehearse)))


def _layer_types(sizes: dict) -> tuple:
    return ("dense",) * sizes["first_dense"] + ("sparse",) * (sizes["n_layer"] - sizes["first_dense"])


def reference_sizes(sizes: dict) -> reference.Sizes:
    return reference.Sizes(
        num_attention_heads=sizes["n_head"], qk_nope_head_dim=sizes["qk_nope_dim"],
        qk_rope_head_dim=sizes["qk_rope_dim"], v_head_dim=sizes["v_head_dim"], kv_lora_rank=sizes["kv_lora_rank"],
        num_experts_per_tok=sizes["expert_top_k"], routed_scaling_factor=sizes["routed_scaling"],
        layer_types=_layer_types(sizes), rms_norm_eps=sizes["rms_eps"], rope_theta=sizes["rope_theta"],
        experts_held=sizes["experts_held"])


def reference_loss(config: dict, params, tokens, targets, rehearse: bool = False) -> float:
    """Mean next-token loss of the plain float32 reference on the program's
    parameter tree (an expert cast up at a time), rows one at a time."""
    return reference.loss(params, tokens, targets, s=reference_sizes(shape(config, rehearse)))


def watched_layers(params) -> tuple[int, ...]:
    """The dense layer 0 and the last expert layer. Every kernel call of the
    step's backward lies in one of them or between, so a fault in any reaches
    their gradients through the cotangent of the residual stream."""
    return (0, len(params["layers"]) - 1)


WATCHED_EXPERTS = 7
"""How many of the last layer's held experts the first-moment comparison
holds: its 64 are 1.2 GB in float32 a side (the router, the selection bias,
the shared experts, the attention leaves and the norms are compared whole; the
router under a limit of its own, ``drivers/train_leaf_limits.py``). They are the
layer's busiest on the first batch, by the reference's gradient: an expert
without a row has no gradient to hold anything to."""


def watched_view(layer_tree: dict, like: dict) -> dict:
    """``layer_tree`` (a whole layer's parameters or moments) cut to the experts
    that ``like`` (that layer's reference gradient) holds, each expert a subtree
    of its own (``moe.experts[e]``); a dense layer whole."""
    if "moe" not in like:
        return layer_tree
    return reference.watched_leaves(layer_tree, like["moe"]["experts"])


def reference_layer_grads(config: dict, params, tokens, targets, rehearse: bool = False,
                          precision: str = "float32", experts: dict | None = None) -> dict:
    """``{i: float32 gradient tree}`` of the plain reference's mean loss, for
    the watched layers and the expert layer's ``WATCHED_EXPERTS`` busiest
    experts (``watched_view`` cuts the program's trees to match), or for the
    ``experts`` given (``{layer: indices}``: a control is held to the experts
    the float32 reference chose). ``precision`` names the reference's variant
    (``reference.VARIANTS``): ``float32`` or one of the deliberate faults."""
    held = params["layers"][-1]["moe"]["w_gate"].shape[0]
    return reference.layer_grads(params, tokens, targets, watched_layers(params),
                                 s=reference_sizes(shape(config, rehearse)), variant=precision,
                                 experts=experts, busiest=0 if experts else min(WATCHED_EXPERTS, held))


# -- the family's own counts ---------------------------------------------------

def _held(shape: dict) -> int:
    return shape["experts_held"][1] if shape["experts_held"] else shape["n_experts"]


def _n_sparse(shape: dict) -> int:
    return shape["n_layer"] - shape["first_dense"]


def _qk(shape: dict) -> int:
    return shape["qk_nope_dim"] + shape["qk_rope_dim"]


def parameter_count(shape: dict) -> int:
    d, h, r = shape["d_model"], shape["n_head"], shape["kv_lora_rank"]
    mla = (d * h * _qk(shape) + d * (r + shape["qk_rope_dim"]) + r
           + r * h * (shape["qk_nope_dim"] + shape["v_head_dim"]) + h * shape["v_head_dim"] * d)
    dense = mla + 3 * d * shape["dense_d_ff"] + 2 * d
    sparse = (mla + 3 * d * shape["n_shared_experts"] * shape["d_ff"] + d * shape["n_experts"] + shape["n_experts"]
              + 2 * d + _held(shape) * 3 * d * shape["d_ff"])
    return shape["first_dense"] * dense + _n_sparse(shape) * sparse + 2 * shape["vocab_size"] * d + d


def _attention_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """q·kᵀ at the query-key width and p·v at the value width, every head,
    forward, over the causal pairs a row has."""
    pairs = seq * (seq + 1) // 2
    return shape["n_layer"] * (int(n_tokens) // seq) * pairs * 2 * shape["n_head"] * (_qk(shape) + shape["v_head_dim"])


def _pairs_held(shape: dict, n_tokens: int) -> float:
    """The (token, expert) pairs a layer's held experts get at uniform routing."""
    return int(n_tokens) * shape["expert_top_k"] * _held(shape) / shape["n_experts"]


def _expert_flops(shape: dict, n_tokens: int) -> float:
    """The three matmuls of every (token, held expert) pair at uniform
    routing, forward, over the expert layers; no padding row counted."""
    return _n_sparse(shape) * _pairs_held(shape, n_tokens) * 3 * 2 * shape["d_model"] * shape["d_ff"]


def train_flops(shape: dict, n_tokens: int, seq: int) -> float:
    """Model FLOPs of one training step: matmuls and attention only, backward =
    2 x forward, recomputation and the padding of the experts' row tiles not
    counted; the held experts at uniform routing."""
    d, h, r = shape["d_model"], shape["n_head"], shape["kv_lora_rank"]
    mla = (d * h * _qk(shape) + d * (r + shape["qk_rope_dim"])
           + r * h * (shape["qk_nope_dim"] + shape["v_head_dim"]) + h * shape["v_head_dim"] * d)
    per_token = (2 * shape["n_layer"] * mla + shape["first_dense"] * 2 * 3 * d * shape["dense_d_ff"]
                 + _n_sparse(shape) * 2 * (3 * d * shape["n_shared_experts"] * shape["d_ff"] + d * shape["n_experts"])
                 + 2 * d * shape["vocab_size"])
    return 3 * (int(n_tokens) * per_token + _attention_flops(shape, n_tokens, seq) + _expert_flops(shape, n_tokens))


def attention_train_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """The attention term alone, at 192 / 128 over the live causal pairs:
    forward and backward, six matmuls of the pairs (q·kᵀ, p·v; dp, dv, dq, dk)."""
    return 3 * _attention_flops(shape, n_tokens, seq)


def attention_train_bytes(shape: dict, n_tokens: int, bytes_per_value: int = 2) -> int:
    """The least HBM traffic of attention in a training step: q and k read
    (forward) and q, k read and dq, dk written (backward) at the query-key
    width; v read and o written (forward), v, o, do read and dv written
    (backward) at the value width, every head."""
    h = shape["n_head"]
    return shape["n_layer"] * int(n_tokens) * h * (6 * _qk(shape) + 6 * shape["v_head_dim"]) * bytes_per_value


def expert_train_flops(shape: dict, n_tokens: int) -> float:
    """The held experts' matmuls of one training step, forward and backward, at
    uniform routing: the least work of the algorithm, no padding row counted."""
    return 3 * _expert_flops(shape, n_tokens)


def expert_train_bytes(shape: dict, n_tokens: int, bytes_per_value: int = 2) -> float:
    """The least HBM traffic of the experts' matmuls in a training step: each
    held expert's three matrices read twice and their gradient written once;
    the pairs' rows read and written forward, read with their cotangent and
    their cotangent written backward (five ``[pairs, d_model]`` arrays)."""
    weights = 3 * _held(shape) * 3 * shape["d_model"] * shape["d_ff"]
    rows = 5 * _pairs_held(shape, n_tokens) * shape["d_model"]
    return _n_sparse(shape) * (weights + rows) * bytes_per_value
