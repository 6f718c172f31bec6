"""The Mellum family: from a configuration file (the source's own keys) to the
program's model, to the plain reference, and to the family's own counts of
operations and bytes (``benchmarks/flops.py`` counts a dense GPT-2 block: full
causal attention in every layer, two MLP matmuls, full-width k and v).

The program's ``Mellum.init`` draws on the device from the seed, so the
benchmark adds no initialiser of its own.
"""

from __future__ import annotations

from benchmarks.reference import mellum as reference
from dsml_tpu.models.mellum import Mellum, MellumConfig

DTYPE = "bfloat16"


def shape(config: dict, rehearse: bool = False) -> dict:
    """The sizes the arithmetic needs, under the program's names: the keys
    ``drivers/train.py`` and ``flops.py`` read, then the family's own.
    ``rehearse`` swaps in ``MellumConfig.tiny()``'s sizes: a CPU rehearsal of
    the control flow, never a measurement."""
    if rehearse:
        tiny = MellumConfig.tiny()
        return {k: getattr(tiny, k) for k in _KEYS}
    if config["hidden_act"] != "silu" or config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("the Mellum family computes gated SiLU experts, no bias and an untied head")
    if set(config["mlp_layer_types"]) != {"sparse"} or not config["norm_topk_prob"] or not config["use_sliding_window"]:
        raise ValueError("the Mellum family computes an expert layer in every block, renormalised "
                         "top-k weights and a window in the sliding layers")
    rope = config["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default") or full["rope_theta"] != sliding["rope_theta"]:
        raise ValueError("the Mellum family rotates full layers by YaRN and sliding layers plainly, on one theta")
    return {
        "vocab_size": config["vocab_size"],
        "max_seq": config["max_position_embeddings"],
        "n_layer": config["num_hidden_layers"],
        "n_head": config["num_attention_heads"],
        "n_kv_head": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "d_model": config["hidden_size"],
        "d_ff": config["moe_intermediate_size"],
        "n_experts": config["num_experts"],
        "expert_top_k": config["num_experts_per_tok"],
        "experts_held": tuple(config["experts_held"]) if config.get("experts_held") else None,
        "layer_types": tuple(config["layer_types"]),
        "window": config["sliding_window"],
        "rope_theta": float(full["rope_theta"]),
        "yarn_factor": float(full["factor"]),
        "yarn_original_max": full["original_max_position_embeddings"],
        "yarn_beta_fast": float(full["beta_fast"]),
        "yarn_beta_slow": float(full["beta_slow"]),
        "yarn_attention_factor": full["attention_factor"],
        "rms_eps": config["rms_norm_eps"],
    }


_KEYS = ("vocab_size", "max_seq", "n_layer", "n_head", "n_kv_head", "head_dim", "d_model", "d_ff", "n_experts",
         "expert_top_k", "experts_held", "layer_types", "window", "rope_theta", "yarn_factor",
         "yarn_original_max", "yarn_beta_fast", "yarn_beta_slow", "yarn_attention_factor", "rms_eps")


def program_model(config: dict, rehearse: bool = False) -> Mellum:
    assumed = config["assumed"]
    tile = MellumConfig.tiny().expert_tile if rehearse else int(assumed["expert_tile"].split()[0])
    return Mellum(MellumConfig(dtype=DTYPE, remat=assumed["remat"].startswith("whole block"), expert_tile=tile,
                               **shape(config, rehearse)))


def reference_sizes(sizes: dict) -> reference.Sizes:
    return reference.Sizes(
        num_attention_heads=sizes["n_head"], num_key_value_heads=sizes["n_kv_head"], head_dim=sizes["head_dim"],
        num_experts_per_tok=sizes["expert_top_k"], sliding_window=sizes["window"],
        layer_types=sizes["layer_types"], rms_norm_eps=sizes["rms_eps"], rope_theta=sizes["rope_theta"],
        yarn_factor=sizes["yarn_factor"], yarn_original_max=sizes["yarn_original_max"],
        yarn_beta_fast=sizes["yarn_beta_fast"], yarn_beta_slow=sizes["yarn_beta_slow"],
        yarn_attention_factor=sizes["yarn_attention_factor"], experts_held=sizes["experts_held"])


def reference_loss(config: dict, params, tokens, targets, rehearse: bool = False) -> float:
    """Mean next-token loss of the plain float32 reference on the program's
    parameter tree (an expert cast up at a time), rows one at a time."""
    return reference.loss(params, tokens, targets, s=reference_sizes(shape(config, rehearse)))


def watched_layers(params) -> tuple[int, ...]:
    """The lowest sliding layer, 0, and the full layer that closes the period.
    Every kernel call of the step's backward lies in one of them or above the
    lower, so a fault in any reaches their gradients through the cotangent of
    the residual stream."""
    return (0, len(params["layers"]) - 1)


WATCHED_EXPERTS = 7
"""How many of a watched layer's experts the first-moment comparison holds: a
layer's 64 are 1.6 GB in float32 a side, and the step needs what the chip has
left beside its 10.7 GB of state (the router, the attention leaves and the norms
are compared whole). They are the layer's busiest on the first batch, by the
reference's gradient: at random weights the deeper layers route every token to
much the same few experts, and an expert without a row has no gradient to hold
anything to."""


def watched_view(layer_tree: dict, like: dict) -> dict:
    """``layer_tree`` (a whole layer's parameters or moments) cut to the experts
    that ``like`` (that layer's reference gradient) holds, each expert a subtree
    of its own (``moe.experts[e]``)."""
    return reference.watched_leaves(layer_tree, like["moe"]["experts"])


def reference_layer_grads(config: dict, params, tokens, targets, rehearse: bool = False,
                          precision: str = "float32", experts: dict | None = None) -> dict:
    """``{i: float32 gradient tree}`` of the plain reference's mean loss, for
    the watched layers and each one's ``WATCHED_EXPERTS`` busiest experts
    (``watched_view`` cuts the program's trees to match), or for the ``experts``
    given (``{layer: indices}``: a control is held to the experts the float32
    reference chose). ``precision`` names the reference's variant
    (``reference.VARIANTS``): ``float32`` or one of the deliberate faults."""
    held = params["layers"][0]["moe"]["w_gate"].shape[0]
    return reference.layer_grads(params, tokens, targets, watched_layers(params),
                                 s=reference_sizes(shape(config, rehearse)), variant=precision,
                                 experts=experts, busiest=0 if experts else min(WATCHED_EXPERTS, held))


# -- the family's own counts ---------------------------------------------------

def parameter_count(shape: dict) -> int:
    d, f = shape["d_model"], shape["d_ff"]
    q_d, kv_d = shape["n_head"] * shape["head_dim"], shape["n_kv_head"] * shape["head_dim"]
    held = shape["experts_held"][1] if shape["experts_held"] else shape["n_experts"]
    layer = 2 * d * q_d + 2 * d * kv_d + d * shape["n_experts"] + 2 * d + held * 3 * d * f
    return shape["n_layer"] * layer + 2 * shape["vocab_size"] * d + d


def attention_pairs(kind: str, seq: int, window: int) -> int:
    """(query, key) pairs a row of ``seq`` tokens really has in a layer of
    ``kind``: ``S(S+1)/2`` full, ``W·S - W(W-1)/2`` under a window of ``W <= S``."""
    w = min(window, seq) if kind == "sliding_attention" else seq
    return w * seq - w * (w - 1) // 2


def _attention_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """q·kᵀ and p·v over every query head, forward, over the pairs each layer has."""
    pairs = sum(attention_pairs(kind, seq, shape["window"]) for kind in shape["layer_types"])
    return (int(n_tokens) // seq) * pairs * 2 * 2 * shape["n_head"] * shape["head_dim"]


def _expert_flops(shape: dict, n_tokens: int) -> int:
    """The three matmuls of every (token, expert) pair, forward, over all layers.
    With a share of the experts held the pairs here are the router's to give; a
    cell that holds all of them has ``tokens x top_k``."""
    return shape["n_layer"] * int(n_tokens) * shape["expert_top_k"] * 3 * 2 * shape["d_model"] * shape["d_ff"]


def train_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """Model FLOPs of one training step: matmuls and attention only, backward =
    2 x forward, recomputation and the padding of the experts' row tiles not
    counted."""
    d = shape["d_model"]
    q_d, kv_d = shape["n_head"] * shape["head_dim"], shape["n_kv_head"] * shape["head_dim"]
    per_token = (shape["n_layer"] * 2 * (2 * d * q_d + 2 * d * kv_d + d * shape["n_experts"])
                 + 2 * d * shape["vocab_size"])
    return 3 * (int(n_tokens) * per_token + _attention_flops(shape, n_tokens, seq) + _expert_flops(shape, n_tokens))


def attention_train_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """The attention term alone, over the pairs each layer type really has (a
    full-causal count would put ``flash_roofline`` at 2.4 times its value)."""
    return 3 * _attention_flops(shape, n_tokens, seq)


def attention_train_bytes(shape: dict, n_tokens: int, bytes_per_value: int = 2) -> int:
    """The least HBM traffic of attention in a training step: q, o (forward)
    and q, o, do, dq (backward) are ``n_head·head_dim`` wide; k, v (forward) and
    k, v, dk, dv (backward) as wide as the key-value heads there are."""
    q_d, kv_d = shape["n_head"] * shape["head_dim"], shape["n_kv_head"] * shape["head_dim"]
    return shape["n_layer"] * 6 * int(n_tokens) * (q_d + kv_d) * bytes_per_value


def expert_train_flops(shape: dict, n_tokens: int) -> int:
    """The experts' matmuls of one training step, forward and backward: the
    least work of the algorithm, the same whatever implements it."""
    return 3 * _expert_flops(shape, n_tokens)


def expert_train_bytes(shape: dict, n_tokens: int, bytes_per_value: int = 2) -> int:
    """The least HBM traffic of the experts' matmuls in a training step: each
    held expert's three matrices read twice (forward, backward) and their
    gradient written once; the pairs' rows read and written forward, read with
    their cotangent and their cotangent written backward (five ``[pairs,
    d_model]`` arrays; the ``d_ff``-wide middle never has to leave the chip)."""
    held = shape["experts_held"][1] if shape["experts_held"] else shape["n_experts"]
    weights = 3 * held * 3 * shape["d_model"] * shape["d_ff"]
    rows = 5 * int(n_tokens) * shape["expert_top_k"] * shape["d_model"]
    return shape["n_layer"] * (weights + rows) * bytes_per_value
