"""A decoder walked over a list of layer types: the embedding, then each
layer by its own type, each block computed again in the backward (whole-block
``jax.checkpoint``) but for what :data:`KEPT` names: the integers of its
expert layer's routing, and the flash forward kernel's ``out`` and ``lse``, so
that the recomputed block does not run the attention kernel again. One walk
for the families whose layers are not all alike and whose feed-forwards are
expert layers (``models/mellum.py``: sliding and full attention;
``models/deepseek_v3.py``: a dense and a sparse feed-forward).

:class:`LayerStack` overrides :class:`~dsml_tpu.models.llama.Llama` where the
walk differs and asks its family for four things: the type of each layer
(``_kinds``), what a step computes once for all layers of a type (``_tables``:
rotary tables), and a block's attention (``_attention``) and feed-forward
(``_feed_forward``) by its type. A family may also apply the layers more
than once a step with the same parameters (``passes``, 1 here), each pass
under the name ``ut_step`` and handing on what ``_pass_end`` makes of its
output (``models/ouro.py``). A family checks the mesh axes and attention
implementations it can run (``_check_axes``); ``tp``, ``sp`` / ``cp`` and ``pp``
raise in both (an exchange of rows between chips is ROADMAP Reach 2; a pipeline
stacks like layers on a leading axis, and this stack holds unlike ones).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax import lax

from dsml_tpu.models.common import fsdp_spec_fn
from dsml_tpu.models.experts import PLAN_NAMES, route
from dsml_tpu.models.llama import Llama, _rms_norm
from dsml_tpu.ops.flash import FLASH_OUTPUTS

__all__ = ["KEPT", "LayerStack", "no_serving"]

# what whole-block recomputation keeps: the integers of each layer's routing (`PLAN_NAMES`)
# and the flash forward kernel's two outputs, so that kernel runs once a step, not twice
KEPT = jax.checkpoint_policies.save_only_these_names(*PLAN_NAMES, FLASH_OUTPUTS)


class LayerStack(Llama):
    """The walk over ``_kinds()`` (see module docstring)."""

    # ---- what a family supplies ------------------------------------------------

    def _kinds(self) -> tuple[str, ...]:
        """Each layer's type, bottom up."""
        raise NotImplementedError

    def _tables(self, positions) -> dict:
        """``{layer type: what its blocks take beside the layer and h}``,
        made once a step from the positions."""
        raise NotImplementedError

    def _attention(self, layer, h, table, kind: str):
        """The attention's output on ``h``, before the residual add."""
        raise NotImplementedError

    def _feed_forward(self, layer, h, kind: str):
        """``h`` after the block's feed-forward, the residual add included."""
        raise NotImplementedError

    def _check_axes(self, tp_axis, sp_axis, attn_impl) -> None:
        """Raise for the mesh axes and attention implementations the family does not compute."""
        raise NotImplementedError

    @property
    def passes(self) -> int:
        """How many times a step applies the layers, the same parameters each time."""
        return 1

    def _pass_end(self, params, h):
        """What a pass hands on, to the next pass and to the loss: ``h`` itself."""
        return h

    # ---- the walk ------------------------------------------------------------

    def _block_fn(self, kind: str):
        """``(layer, h, table) -> h``: one block of type ``kind``."""
        def run(layer, h, table):
            with jax.named_scope("attn"):
                h = h + self._attention(layer, h, table, kind)
            return self._feed_forward(layer, h, kind)

        return run

    def param_specs(self, pp: bool = False, fsdp: int = 1) -> dict:
        """Replicated but for ZeRO sharding over ``fsdp`` (each leaf on its
        first divisible dim, ``models.common.with_fsdp``)."""
        from jax.sharding import PartitionSpec as P

        if pp:
            raise NotImplementedError(
                f"{type(self).__name__}: pp stacks like layers on a leading axis; this stack holds two kinds")
        shapes = jax.eval_shape(lambda: self.init(0))
        spec = fsdp_spec_fn(fsdp)
        return jax.tree.map(lambda leaf: spec(P(), *leaf.shape), shapes)

    def _block_closure(self, tp_axis, sp_axis, attn_impl):
        self._check_axes(tp_axis, sp_axis, attn_impl)
        blocks = {kind: self._block_fn(kind) for kind in set(self._kinds())}
        if self.config.remat:
            blocks = {kind: jax.checkpoint(run, policy=KEPT) for kind, run in blocks.items()}
        return blocks

    def _walk(self, params, tokens, blocks, upto: int | None = None, tp_axis=None, sp_axis=None):
        """The embedding, then ``passes`` times layers ``[0, upto)`` each by
        its own type, each pass's output through ``_pass_end``: ``(states,
        tables)``, ``states`` the state after each pass."""
        tables = self._tables(jnp.arange(tokens.shape[1], dtype=jnp.int32))
        h = self._embed_spmd(params, tokens, tp_axis, sp_axis)
        states = []
        for _ in range(self.passes):
            with jax.named_scope("ut_step") if self.passes > 1 else contextlib.nullcontext():
                for kind, layer in zip(self._kinds()[:upto], params["layers"][:upto]):
                    h = blocks[kind](layer, h, tables[kind])
                h = self._pass_end(params, h)
            states.append(h)
        return states, tables

    def _blocks_spmd(self, params, tokens, tp_axis=None, sp_axis=None, attn_impl="ring",
                     seq_offset=None, pp_axis=None, n_micro=1):
        """Embedding, then the layers one after another, each by its own type."""
        if pp_axis:
            raise NotImplementedError(f"{type(self).__name__}: no pipeline over unlike layers (see param_specs)")
        blocks = self._block_closure(tp_axis, sp_axis, attn_impl)
        return self._walk(params, tokens, blocks, tp_axis=tp_axis, sp_axis=sp_axis)[0][-1]

    def expert_load(self, params, tokens, layer: int | None = None):
        """The (token, expert) pairs each expert of ``layer`` (by default the
        lowest expert layer) gets from ``tokens [b, s]`` under ``params``,
        ``[n_experts]`` int32: a counter (the benchmark's ``moe_load_max``),
        computed by the program's own forward up to that layer's router."""
        cfg = self.config
        kinds = self._kinds()
        if layer is None:
            layer = next(i for i, p in enumerate(params["layers"]) if "moe" in p)
        states, tables = self._walk(params, tokens, self._block_closure(None, None, "flash"), upto=layer)
        h = states[-1]
        kind, p = kinds[layer], params["layers"][layer]
        h = h + self._attention(p, h, tables[kind], kind)
        x = _rms_norm(h, p["rms_2"]["scale"], cfg.rms_eps)
        # the choice alone: a sigmoid router's scale moves the weights, not which experts are taken
        top_e, _ = route(x.reshape(-1, x.shape[-1]), p["moe"]["router"], cfg.expert_top_k, p["moe"].get("bias"))
        return jnp.sum(top_e.reshape(-1, 1) == jnp.arange(cfg.n_experts), axis=0, dtype=jnp.int32)

    def _sharded(self, tp_axis, sp_axis) -> dict:
        """``{axis: size}`` of the axes among ``tp_axis`` / ``sp_axis`` that shard."""
        return {axis: lax.axis_size(axis) for axis in (tp_axis, sp_axis) if axis and lax.axis_size(axis) > 1}


def no_serving(cls, why: str) -> None:
    """Every serving entry point of ``cls`` raises ``NotImplementedError``
    with ``why``."""
    def entry_of(name: str):
        def entry(self, *args, **kwargs):
            raise NotImplementedError(f"{cls.__name__}.{name}: {why}")

        entry.__name__ = name
        return entry

    for name in ("init_cache", "prefill", "prefill_chunk", "decode_step", "decode_step_slots",
                 "verify_step", "init_page_pool", "prefill_chunk_paged", "decode_step_slots_paged",
                 "verify_step_paged", "generate", "generate_spmd"):
        setattr(cls, name, entry_of(name))
