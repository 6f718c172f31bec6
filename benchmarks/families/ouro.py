"""The Ouro family: from a configuration file (the source's own keys) to the
program's model, to the plain reference, and to the family's own counts of
operations and bytes (``benchmarks/flops.py`` counts a dense GPT-2 block run
once with one head).

The program's ``Ouro.init`` draws on the device from the seed, so the benchmark
adds no initialiser of its own.
"""

from __future__ import annotations

from benchmarks.reference import ouro as reference
from dsml_tpu.models.ouro import Ouro, OuroConfig

DTYPE = "bfloat16"


def shape(config: dict, rehearse: bool = False) -> dict:
    """The sizes the arithmetic needs, under the program's names: the keys
    ``drivers/train.py`` reads, then the family's own. ``rehearse`` swaps in
    ``OuroConfig.tiny()``'s sizes: a CPU rehearsal of the control flow, never a
    measurement."""
    if rehearse:
        tiny = OuroConfig.tiny()
        return {k: getattr(tiny, k) for k in _KEYS}
    if config["hidden_act"] != "silu" or config["tie_word_embeddings"] or config["rope_scaling"] is not None:
        raise ValueError("the Ouro family computes a gated SiLU MLP, an untied head and rotary without scaling")
    if (config["use_sliding_window"] or set(config["layer_types"]) != {"full_attention"}
            or len(config["layer_types"]) != config["num_hidden_layers"]):
        raise ValueError("the Ouro family computes full causal attention in every layer")
    if config["num_key_value_heads"] != config["num_attention_heads"] or config["early_exit_threshold"] != 1:
        raise ValueError("the Ouro family computes one key-value head a query head and every exit of every pass")
    return {
        "vocab_size": config["vocab_size"],
        "max_seq": config["max_position_embeddings"],
        "n_layer": config["num_hidden_layers"],
        "n_head": config["num_attention_heads"],
        "n_kv_head": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "d_model": config["hidden_size"],
        "d_ff": config["intermediate_size"],
        "total_ut_steps": config["total_ut_steps"],
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": config["rms_norm_eps"],
        "entropy_weight": float(config["assumed"]["entropy_weight"].split(":")[0]),
    }


_KEYS = ("vocab_size", "max_seq", "n_layer", "n_head", "n_kv_head", "head_dim", "d_model", "d_ff",
         "total_ut_steps", "rope_theta", "rms_eps", "entropy_weight")


def program_model(config: dict, rehearse: bool = False) -> Ouro:
    return Ouro(OuroConfig(dtype=DTYPE, remat=config["assumed"]["remat"].startswith("whole block"),
                           **shape(config, rehearse)))


def reference_sizes(sizes: dict) -> reference.Sizes:
    return reference.Sizes(num_attention_heads=sizes["n_head"], head_dim=sizes["head_dim"],
                           total_ut_steps=sizes["total_ut_steps"], rms_norm_eps=sizes["rms_eps"],
                           rope_theta=sizes["rope_theta"], entropy_weight=sizes["entropy_weight"])


def reference_loss(config: dict, params, tokens, targets, rehearse: bool = False) -> float:
    """The plain float32 reference's loss on the program's parameter tree (one
    layer cast up at a time), rows one at a time."""
    return reference.loss(params, tokens, targets, s=reference_sizes(shape(config, rehearse)))


def watched_layers(layers) -> tuple[int, ...]:
    """The first and the last layer. Every block application of the step (each
    layer once a pass) lies between the last layer of the last pass and the
    first of the first, so a fault in any reaches their gradients through the
    cotangent of the residual stream."""
    return (0, len(layers) - 1)


def watched_view(tree: dict) -> dict:
    """What the first-moment comparison holds of a tree shaped like the
    parameters: the watched layers, the exit gate and the final norm."""
    layers = tree["layers"]
    return {"layers": {i: layers[i] for i in watched_layers(layers)},
            "exit_gate": tree["exit_gate"], "rms_f": tree["rms_f"]}


def reference_grads(config: dict, params, tokens, targets, rehearse: bool = False,
                    variant: str = "float32") -> dict:
    """The float32 gradient of the plain reference's loss, shaped as
    ``watched_view`` cuts the parameters, and beside it ``exit_gate_terms``,
    the sum of the magnitudes of the terms of the gate's bias's gradient (the
    scale ``drivers/train_looped.py`` judges the bias by). ``variant`` names the
    reference's variant (``reference.VARIANTS``): ``float32`` or one of the
    deliberate faults."""
    return reference.grads(params, tokens, targets, watched_layers(params["layers"]),
                           s=reference_sizes(shape(config, rehearse)), variant=variant)


# -- the family's own counts ---------------------------------------------------

def _layer(shape: dict) -> int:
    """One layer's matrices: q, k, v, o and the gated MLP."""
    d, hd = shape["d_model"], shape["head_dim"]
    return d * hd * (2 * shape["n_head"] + 2 * shape["n_kv_head"]) + 3 * d * shape["d_ff"]


def parameter_count(shape: dict) -> int:
    d = shape["d_model"]
    return shape["n_layer"] * (_layer(shape) + 4 * d) + 2 * shape["vocab_size"] * d + d + d + 1


def _attention_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """q·kᵀ and p·v of every head over the causal pairs a row has, forward, in
    every block application."""
    pairs = seq * (seq + 1) // 2
    applications = shape["n_layer"] * shape["total_ut_steps"]
    return applications * (int(n_tokens) // seq) * pairs * 2 * 2 * shape["n_head"] * shape["head_dim"]


def train_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """Model FLOPs of one training step: matmuls and attention only, backward =
    2 x forward, recomputation not counted; the layers' matmuls and attention in
    each of the ``total_ut_steps`` passes, the head at each exit, the gate at
    every exit but the last."""
    d, passes = shape["d_model"], shape["total_ut_steps"]
    per_token = (passes * 2 * shape["n_layer"] * _layer(shape) + passes * 2 * d * shape["vocab_size"]
                 + (passes - 1) * 2 * d)
    return 3 * (int(n_tokens) * per_token + _attention_flops(shape, n_tokens, seq))


def attention_train_flops(shape: dict, n_tokens: int, seq: int) -> int:
    """The attention term alone: forward and backward, six matmuls of the
    causal pairs (q·kᵀ, p·v; dp, dv, dq, dk), every block application."""
    return 3 * _attention_flops(shape, n_tokens, seq)


def attention_train_bytes(shape: dict, n_tokens: int, bytes_per_value: int = 2) -> int:
    """The least HBM traffic of attention in a training step: q, k, v read and
    o written (forward); q, k, v, o, do read and dq, dk, dv written (backward),
    twelve ``[tokens, heads x head_dim]`` arrays a block application."""
    applications = shape["n_layer"] * shape["total_ut_steps"]
    return applications * 12 * int(n_tokens) * shape["n_head"] * shape["head_dim"] * bytes_per_value
