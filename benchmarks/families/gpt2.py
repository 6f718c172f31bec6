"""The GPT-2 family: from a configuration file (the source's own keys) to the
program's model, to weights made on the device, and to the plain reference.

The program's ``GPT2.init`` draws every leaf on the host with numpy (18 s for
GPT-2-large in the sandbox); a run pays set-up in every check of every later
PR, so the benchmark makes the weights itself: one jitted call from the seed,
on the device, in the dtype they are trained in, with the program's own
standard deviations. ``init_hybrid`` still places them and builds the optimizer
state: ``DeviceInitGPT2`` only replaces the draw.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import gpt2 as reference
from dsml_tpu.models.gpt2 import GPT2, GPT2Config

DTYPE = "bfloat16"


def shape(config: dict, rehearse: bool = False) -> dict:
    """The sizes the arithmetic needs, under the program's names. ``rehearse``
    swaps in ``GPT2Config.tiny()``'s sizes: a CPU rehearsal of the control
    flow, never a measurement."""
    if rehearse:
        tiny = GPT2Config.tiny()
        return {"vocab_size": tiny.vocab_size, "max_seq": tiny.max_seq, "n_layer": tiny.n_layer,
                "n_head": tiny.n_head, "d_model": tiny.d_model, "d_ff": tiny.d_ff}
    if config["activation_function"] != "gelu_new":
        raise ValueError(f"the GPT-2 family computes gelu_new, not {config['activation_function']!r}")
    d_model = config["n_embd"]
    return {
        "vocab_size": config["vocab_size"],
        "max_seq": config["n_positions"],
        "n_layer": config["n_layer"],
        "n_head": config["n_head"],
        "d_model": d_model,
        "d_ff": config["n_inner"] or 4 * d_model,
    }


@functools.partial(jax.jit, static_argnames=("vocab_size", "max_seq", "n_layer", "d_model", "d_ff"))
def _draw(key, *, vocab_size, max_seq, n_layer, d_model, d_ff):
    """Every leaf of ``GPT2.init``'s tree, same shapes and standard deviations
    (0.02; 0.01 for positions; 0.02/sqrt(2 n_layer) on the residual-path
    projections), drawn per kind for all layers at once and split."""
    dt = jnp.dtype(DTYPE)
    res_std = 0.02 / math.sqrt(2 * n_layer)
    keys = iter(jax.random.split(key, 6))

    def normal(std, *dims):
        return (jax.random.normal(next(keys), dims, jnp.float32) * std).astype(dt)

    def norm():
        return {"scale": jnp.ones(d_model, dt), "bias": jnp.zeros(d_model, dt)}

    wqkv = normal(0.02, n_layer, d_model, 3, d_model)
    wo = normal(res_std, n_layer, d_model, d_model)
    w_in = normal(0.02, n_layer, d_model, d_ff)
    w_out = normal(res_std, n_layer, d_ff, d_model)
    layers = [{
        "ln_1": norm(),
        "ln_2": norm(),
        "attn": {"wqkv": wqkv[i], "bqkv": jnp.zeros((3, d_model), dt),
                 "wo": wo[i], "bo": jnp.zeros(d_model, dt)},
        "mlp": {"w_in": w_in[i], "b_in": jnp.zeros(d_ff, dt),
                "w_out": w_out[i], "b_out": jnp.zeros(d_model, dt)},
    } for i in range(n_layer)]
    return {"wte": normal(0.02, vocab_size, d_model), "wpe": normal(0.01, max_seq, d_model),
            "ln_f": norm(), "layers": layers}


class DeviceInitGPT2(GPT2):
    """The program's GPT-2 with the weights drawn on the device."""

    def init(self, seed: int = 0) -> dict:
        cfg = self.config
        return _draw(jax.random.key(seed), vocab_size=cfg.vocab_size, max_seq=cfg.max_seq,
                     n_layer=cfg.n_layer, d_model=cfg.d_model, d_ff=cfg.d_ff)


def program_model(config: dict, rehearse: bool = False) -> GPT2:
    return DeviceInitGPT2(GPT2Config(dtype=DTYPE, **shape(config, rehearse)))


def reference_loss(config: dict, params, tokens, targets, rehearse: bool = False) -> float:
    """Mean next-token loss of the plain float32 reference on the program's
    parameter tree (cast up inside), rows one at a time."""
    return reference.loss(params, tokens, targets, n_head=shape(config, rehearse)["n_head"],
                          eps=config["layer_norm_epsilon"])
