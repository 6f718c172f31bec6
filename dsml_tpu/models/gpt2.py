"""GPT-2 — the flagship transformer, designed as an SPMD mesh program.

BASELINE.md's top config is "TinyStories GPT-2-small (125M), data-parallel +
grad accumulation"; the reference itself never got past an MLP (SURVEY.md
§2.3), with TP/SP/hybrid existing only in its literature corpus (Megatron
PTD-P, Ring Self-Attention, LoongTrain 2D attention). This module implements
that roadmap TPU-first:

- **TP** (Megatron-style): QKV/MLP-in weights column-sharded, out-projections
  row-sharded over the ``tp`` axis, ONE ``psum`` per attention block and one
  per MLP block; the unembedding is vocab-sharded with a
  distributed-logsumexp cross-entropy so full logits never materialize.
- **SP/CP**: the sequence axis is sharded over ``sp`` (legacy XLA ring /
  Ulysses) or the ``cp`` context-parallel axis (``attn_impl="ring2"``: the
  bidirectional flash ring with causal hop skipping and a KV re-streaming
  backward, ``ops.ring_attention``) — the model is axis-name-generic, the
  hybrid step passes whichever axis the mesh sizes; LoongTrain's 2D
  head×context grid is exactly ``tp × sp`` here.
- **DP**: batch axis sharded over ``dp``; gradients ``psum`` over (dp, sp).
- **EP (MoE)**: optionally the MLP is a top-k-gated expert layer with experts
  sharded over ``tp`` and token dispatch via ``all_to_all``.

Everything below is shape-static, scan-free Python-loop-over-layers (unrolled
by trace), bf16-friendly, and runs under ``jax.shard_map`` on the framework
mesh (``dsml_tpu.parallel.mesh``). ``apply``/``loss`` (no axis names) give
the plain single-device semantics used for parity tests.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dsml_tpu.models.common import fsdp_spec_fn, head_dim, maybe_dequant, qmatmul
from dsml_tpu.ops.attention import _NEG_INF, attention, ring_attention, ulysses_attention

__all__ = ["GPT2Config", "GPT2"]


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_seq: int = 1024
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 3072
    dtype: str = "float32"  # params/activations dtype ("bfloat16" for TPU runs)
    # MoE: 0 experts = dense MLP; otherwise top-k gated expert layer
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    # rematerialization: recompute each block's activations in the backward
    # pass instead of storing them — trades FLOPs for HBM (the memory-
    # efficiency capability of the reference's §7 literature, ActNN/GACT).
    # True = plain jax.checkpoint (full-precision input stash); "int8" =
    # compressed remat (ops.quantization.compressed_checkpoint): the stash is
    # blockwise-int8, 4x smaller again, gradients exact in expectation
    remat: bool | str = False
    # unsharded-vocab losses above this vocabulary sweep the tokens in blocks
    # (ops/xent.py, which sizes the blocks from the shapes) instead of
    # materializing [tokens, vocab] logits: a threshold and nothing else
    # (vocab_size > xent_chunk; 0 = off, the dense head)
    xent_chunk: int = 8192
    # interleaved virtual pipeline stages (Megatron PTD-P): each pp rank
    # holds this many non-contiguous layer chunks; >1 shrinks the pipeline
    # bubble by the same factor (parallel.pp.pipeline_apply_interleaved).
    # Requires n_layer divisible by pp×pp_interleave; gpipe schedule only
    pp_interleave: int = 1
    # serving: store the KV cache int8 with a per-(b, h, position) scale —
    # ~4x below the f32 cache / 2x below bf16 in both HBM footprint and
    # decode read bandwidth (the cache read IS the decode bottleneck at
    # long context). Dequantized at the attention boundary; prefill/decode/
    # decode_step_slots and both model families share the one code path
    kv_quant: bool | str = False  # False | True/"int8" | "int4"

    @staticmethod
    def small() -> "GPT2Config":
        """GPT-2-small, 125M params (the BASELINE config)."""
        return GPT2Config()

    @staticmethod
    def medium() -> "GPT2Config":
        """GPT-2-medium, 350M params."""
        return GPT2Config(n_layer=24, n_head=16, d_model=1024, d_ff=4096)

    @staticmethod
    def large() -> "GPT2Config":
        """GPT-2-large, 774M params."""
        return GPT2Config(n_layer=36, n_head=20, d_model=1280, d_ff=5120)

    @staticmethod
    def xl() -> "GPT2Config":
        """GPT-2-XL, 1.5B params."""
        return GPT2Config(n_layer=48, n_head=25, d_model=1600, d_ff=6400)

    @classmethod
    def by_name(cls, name: str, **tiny_kwargs) -> "GPT2Config":
        """Preset lookup over the EXPLICIT family ({tiny, small, medium,
        large, xl}) — a raw getattr would accept any class attribute and
        fail obscurely."""
        presets = {"tiny": cls.tiny, "small": cls.small, "medium": cls.medium,
                   "large": cls.large, "xl": cls.xl}
        if name not in presets:
            raise ValueError(f"unknown GPT-2 preset {name!r}; choose from {sorted(presets)}")
        return presets[name](**tiny_kwargs) if name == "tiny" else presets[name]()

    @staticmethod
    def tiny(vocab_size: int = 512, n_experts: int = 0) -> "GPT2Config":
        """Test-sized config that still exercises every code path."""
        return GPT2Config(
            vocab_size=vocab_size, max_seq=128, n_layer=2, n_head=8, d_model=64, d_ff=128,
            n_experts=n_experts,
        )


def sample_token_logits(logits, key, temperature: float, top_k: int = 0,
                        top_p: float = 0.0):
    """Sample next-token ids from ``logits`` [..., vocab] — greedy at
    ``temperature <= 0``, else softmax sampling optionally truncated to the
    ``top_k`` most likely tokens and/or the nucleus holding ``top_p``
    probability mass. THE one sampler shared by ``generate``/
    ``generate_spmd`` and the continuous batcher (host and in-scan paths),
    so the truncation semantics cannot drift between serving surfaces.
    Pure in (logits, key): callers own the key discipline."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p > 0.0:
        # nucleus: keep the smallest prefix (by descending prob) whose mass
        # reaches top_p; always keep the argmax
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # cutoff logit: last sorted position with cum - p < top_p
        keep = (cum - probs) < top_p  # mass BEFORE this token < p
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits >= cutoff, logits, -jnp.inf)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@jax.named_scope("normalize")
def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale + bias


class GPT2:
    """Decoder-only transformer with mesh-aware sharding rules."""

    def __init__(self, config: GPT2Config | None = None):
        self.config = config or GPT2Config.small()
        self._kv_mode()  # a bad kv_quant string fails at construction

    # ---- params ---------------------------------------------------------------

    def init(self, seed: int = 0) -> dict:
        cfg = self.config
        rng = np.random.default_rng(seed)
        dt = jnp.dtype(cfg.dtype)

        def normal(*shape, std=0.02):
            return jnp.asarray(rng.standard_normal(shape) * std, dt)

        def zeros(*shape):
            return jnp.zeros(shape, dt)

        # GPT-2 scales residual-path projections by 1/sqrt(2*n_layer)
        res_std = 0.02 / math.sqrt(2 * cfg.n_layer)
        params = {
            "wte": normal(cfg.vocab_size, cfg.d_model),
            "wpe": normal(cfg.max_seq, cfg.d_model, std=0.01),
            "ln_f": {"scale": jnp.ones(cfg.d_model, dt), "bias": zeros(cfg.d_model)},
            "layers": [],
        }
        for _ in range(cfg.n_layer):
            layer = {
                "ln_1": {"scale": jnp.ones(cfg.d_model, dt), "bias": zeros(cfg.d_model)},
                "ln_2": {"scale": jnp.ones(cfg.d_model, dt), "bias": zeros(cfg.d_model)},
                # wqkv is [d, 3, d] with the LAST dim TP-sharded: a contiguous
                # column shard of a fused [d, 3d] matrix would hand each rank
                # a mix of q/k/v columns and scramble the head assignment
                "attn": {
                    "wqkv": normal(cfg.d_model, 3, cfg.d_model),
                    "bqkv": zeros(3, cfg.d_model),
                    "wo": normal(cfg.d_model, cfg.d_model, std=res_std),
                    "bo": zeros(cfg.d_model),
                },
            }
            if cfg.n_experts:
                layer["moe"] = self._moe_param_init(normal, res_std)
            else:
                layer["mlp"] = {
                    "w_in": normal(cfg.d_model, cfg.d_ff),
                    "b_in": zeros(cfg.d_ff),
                    "w_out": normal(cfg.d_ff, cfg.d_model, std=res_std),
                    "b_out": zeros(cfg.d_model),
                }
            params["layers"].append(layer)
        return params

    def n_params(self, params) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

    # ---- sharding rules (GSPMD specs over the framework mesh axes) -------------

    def param_specs(self, pp: bool = False, fsdp: int = 1) -> dict:
        """PartitionSpec pytree: Megatron TP sharding over 'tp', everything
        else replicated (dp/sp replicate params). With ``pp=True`` the layer
        list is expected STACKED (leading layer axis,
        ``parallel.pp.stack_layer_params``) and sharded over the 'pp' axis so
        each rank holds its pipeline stage. With ``fsdp > 1`` every leaf is
        additionally ZeRO-sharded over the 'fsdp' axis on its first free
        divisible dim (``models.common.with_fsdp``); the hybrid step gathers
        weights just-in-time and reduce-scatters gradients
        (``parallel.hybrid``), so fsdp composes with tp/pp/sp in one mesh."""
        from jax.sharding import PartitionSpec as P

        cfg = self.config
        d, ff, V, S = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.max_seq
        F = fsdp_spec_fn(fsdp)
        layer_spec = {
            "ln_1": {"scale": F(P(), d), "bias": F(P(), d)},
            "ln_2": {"scale": F(P(), d), "bias": F(P(), d)},
            "attn": {
                # column-parallel (heads split); fsdp takes the input dim
                "wqkv": F(P(None, None, "tp"), d, 3, d),
                "bqkv": F(P(None, "tp"), 3, d),
                "wo": F(P("tp", None), d, d),  # row-parallel
                "bo": F(P(), d),
            },
        }
        if cfg.n_experts:
            layer_spec["moe"] = self._moe_specs(fsdp)
        else:
            layer_spec["mlp"] = {
                "w_in": F(P(None, "tp"), d, ff),
                "b_in": F(P("tp"), ff),
                "w_out": F(P("tp", None), ff, d),
                "b_out": F(P(), d),
            }
        if pp:
            from dsml_tpu.parallel.pp import pipeline_specs

            layers_spec = pipeline_specs(layer_spec, "pp")
        else:
            layers_spec = [layer_spec for _ in range(cfg.n_layer)]
        return {
            "wte": F(P("tp", None), V, d),  # vocab-sharded embedding/unembedding
            "wpe": F(P(), S, d),
            "ln_f": {"scale": F(P(), d), "bias": F(P(), d)},
            "layers": layers_spec,
        }

    # ---- forward (per-rank SPMD function; axis names optional) -----------------

    def apply_spmd(
        self,
        params: dict,
        tokens: jax.Array,  # [batch_shard, seq_shard] int32
        tp_axis: str | None = None,
        sp_axis: str | None = None,
        attn_impl: str = "ring",
        seq_offset: int | None = None,
        pp_axis: str | None = None,
        n_micro: int = 1,
    ) -> jax.Array:
        """Per-rank forward to vocab-shard logits.

        Under shard_map: ``tokens`` is this rank's (batch, sequence) shard;
        weights arrive TP-sharded per :meth:`param_specs`. Returns logits
        sharded over tp on the vocab dim: [batch_shard, seq_shard, vocab/tp].

        With ``pp_axis`` set, ``params['layers']`` must be the STACKED stage
        shard (``param_specs(pp=True)``) and the block stack runs as a GPipe
        pipeline of ``n_micro`` microbatches (``parallel.pp``): every rank
        computes the embedding but only stage 0's result enters the pipeline
        (so embedding gradients land on rank 0 alone), activations hop
        stage→stage over ``ppermute``, and the returned logits are replicated
        across pp ranks.
        """
        h = self._hidden_spmd(params, tokens, tp_axis, sp_axis, attn_impl, seq_offset, pp_axis, n_micro)
        return h @ self._unembed_matrix(params).T  # unembedding → [b, s, vocab/tp]

    @jax.named_scope("loss_head")
    def _head_loss_spmd(self, params, h_raw, targets, tp_axis=None):
        """Final norm + tied unembedding + next-token CE for PRE-final-norm
        hidden states ``h_raw`` [b, s, d] → scalar mean loss. The head the
        pipeline's last stage owns; shared by :meth:`loss_spmd` and the 1F1B
        schedule (which must run it per microbatch, inside the schedule)."""
        cfg = self.config
        h = self._final_norm(params, h_raw)
        tp_size = lax.axis_size(tp_axis) if tp_axis else 1
        if tp_size == 1:
            if cfg.xent_chunk and cfg.vocab_size > cfg.xent_chunk:
                # big unsharded vocab: sweep the tokens in blocks — [tokens,
                # vocab] logits never exist (ops/xent.py)
                from dsml_tpu.ops.xent import chunked_softmax_xent

                return chunked_softmax_xent(h, self._unembed_matrix(params), targets)
            logits = (h @ self._unembed_matrix(params).T).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return nll.mean()
        logits = (h @ self._unembed_matrix(params).T).astype(jnp.float32)
        vocab_shard = logits.shape[-1]
        tp_rank = lax.axis_index(tp_axis)
        # distributed logsumexp (max-shift carries no gradient, and pmax has
        # no VJP rule — stop_gradient on both)
        local_max = lax.stop_gradient(logits.max(-1, keepdims=True))
        global_max = lax.stop_gradient(lax.pmax(local_max, tp_axis))
        sumexp = jnp.sum(jnp.exp(logits - global_max), axis=-1, keepdims=True)
        lse = jnp.log(lax.psum(sumexp, tp_axis)) + global_max  # [b, s, 1]
        # target logit lives on exactly one shard
        local_ids = targets - tp_rank * vocab_shard
        in_shard = (local_ids >= 0) & (local_ids < vocab_shard)
        safe_ids = jnp.clip(local_ids, 0, vocab_shard - 1)
        tgt = jnp.take_along_axis(logits, safe_ids[..., None], axis=-1)
        tgt = lax.psum(jnp.where(in_shard[..., None], tgt, 0.0), tp_axis)
        return jnp.mean(lse - tgt)

    @jax.named_scope("embed")
    def _embed_spmd(self, params, tokens, tp_axis=None, sp_axis=None, seq_offset=None):
        """Token + position embedding → [b, s_local, d]. ``wte`` is
        vocab-sharded over tp → masked gather + psum (each token's row lives
        on exactly one shard); positions offset by this rank's sp shard."""
        seq_local = tokens.shape[1]
        if sp_axis:
            sp_rank = lax.axis_index(sp_axis)
            pos = sp_rank * seq_local + jnp.arange(seq_local)
        else:
            # seq_offset may be a traced position (decode steps) — no `or`
            pos = jnp.arange(seq_local) + (0 if seq_offset is None else seq_offset)
        if tp_axis:
            vocab_shard = params["wte"].shape[0]
            tp_rank = lax.axis_index(tp_axis)
            local_ids = tokens - tp_rank * vocab_shard
            in_shard = (local_ids >= 0) & (local_ids < vocab_shard)
            safe_ids = jnp.clip(local_ids, 0, vocab_shard - 1)
            h = lax.psum(params["wte"][safe_ids] * in_shard[..., None], tp_axis)
        else:
            h = params["wte"][tokens]
        return h + params["wpe"][pos]

    def _block_closure(self, tp_axis, sp_axis, attn_impl):
        """``block(one_layer_params, x) -> x`` for the current sharding —
        the unit both pipeline schedules stream microbatches through."""
        cfg = self.config
        tp_size = lax.axis_size(tp_axis) if tp_axis else 1
        if cfg.n_head % tp_size:
            raise ValueError(f"n_head={cfg.n_head} not divisible by tp={tp_size}")
        n_head_local = cfg.n_head // tp_size

        def block(layer, x):
            return self._block(layer, x, n_head_local, tp_axis, sp_axis, attn_impl)

        return block

    def _blocks_spmd(
        self, params, tokens, tp_axis=None, sp_axis=None, attn_impl="ring",
        seq_offset=None, pp_axis=None, n_micro=1,
    ):
        """Embedding + transformer block stack → PRE-final-norm hidden
        states [b, s, d]."""
        cfg = self.config
        if cfg.remat not in (False, True, "int8", "mlp"):
            # a typo ("INT8", "int4") would otherwise silently degrade to
            # plain remat here and to NO remat in the pipeline path
            raise ValueError(
                f"unknown remat mode {cfg.remat!r}; choose False, True, 'int8', or 'mlp'"
            )
        block = self._block_closure(tp_axis, sp_axis, attn_impl)
        h = self._embed_spmd(params, tokens, tp_axis, sp_axis, seq_offset)

        if pp_axis:
            from dsml_tpu.parallel.pp import pipeline_apply, pipeline_apply_interleaved

            b = h.shape[0]
            if b % n_micro:
                raise ValueError(f"per-rank batch {b} not divisible by n_micro={n_micro}")
            micro = h.reshape(n_micro, b // n_micro, *h.shape[1:])
            if cfg.pp_interleave > 1:
                # local stacked layers = this rank's v chunks concatenated
                # (init_hybrid permuted the layer order before sharding);
                # reshape the leading axis to [v, layers_per_chunk]
                v = cfg.pp_interleave
                chunks = jax.tree.map(
                    lambda p: p.reshape(v, p.shape[0] // v, *p.shape[1:]),
                    params["layers"],
                )
                outs = pipeline_apply_interleaved(
                    block, chunks, micro, v, pp_axis,
                    # "mlp" checkpoints inside the block closure itself
                    remat=False if cfg.remat == "mlp" else cfg.remat,
                )
            else:
                # remat at STAGE granularity (one checkpoint per tick) rather
                # than per block — the coarser cut bounds in-flight activations
                # the way 1F1B does
                outs = pipeline_apply(
                    block, params["layers"], micro, pp_axis,
                    remat=False if cfg.remat == "mlp" else cfg.remat,
                )
            h = outs.reshape(b, *h.shape[1:])
        else:
            if cfg.remat == "int8":
                from dsml_tpu.ops.quantization import compressed_checkpoint

                block = compressed_checkpoint(block)
            elif cfg.remat is True:
                # "mlp" (selective) already checkpoints inside _block;
                # wrapping the whole block again would discard the saved
                # attention activations it exists to keep
                block = jax.checkpoint(block)
            for layer in params["layers"]:
                h = block(layer, h)
        return h

    def _hidden_spmd(
        self, params, tokens, tp_axis=None, sp_axis=None, attn_impl="ring",
        seq_offset=None, pp_axis=None, n_micro=1,
    ):
        """Forward to the final-layer-norm hidden states [b, s, d] (shared by
        the logits head and the chunked-xent loss that never builds logits)."""
        h = self._blocks_spmd(
            params, tokens, tp_axis, sp_axis, attn_impl, seq_offset, pp_axis, n_micro
        )
        return self._final_norm(params, h)

    def _block(self, layer, h, n_head_local, tp_axis, sp_axis, attn_impl):
        """One transformer block (pre-LN attention + MLP/MoE residuals) —
        the unit the pipeline schedule streams microbatches through.

        ``remat="mlp"`` is SELECTIVE rematerialization: only the FFN
        sub-block is checkpointed, so the backward pass keeps the
        attention activations (incl. the flash kernel's saved residuals —
        re-running the O(s²·d) attention forward is the expensive part of
        whole-block remat at long context) and recomputes just the two
        cheap O(s·d·ff) FFN matmuls. ~half the activation memory of no
        remat for ~a tenth of whole-block remat's recompute FLOPs."""
        with jax.named_scope("attn"):
            h = h + self._attn_block(layer, h, n_head_local, tp_axis, sp_axis, attn_impl)
        sub, key = ((self._moe_block, "moe") if self.config.n_experts
                    else (self._mlp_block, "mlp"))

        def ffn(sub_p, ln_p, hh):
            with jax.named_scope("mlp"):
                return sub(sub_p, _layer_norm(hh, **ln_p), tp_axis)

        if self.config.remat == "mlp":
            ffn = jax.checkpoint(ffn)
        return h + ffn(layer[key], layer["ln_2"], h)

    _ATTN_IMPLS = ("ring", "ring2", "ulysses", "ulysses_flash", "ring_flash", "flash", "xla")
    _FLASH_IMPLS = ("flash", "ring_flash", "ulysses_flash", "ring2")  # the flash kernels where the sequence is whole

    def _route_attention(self, q, k, v, sp_axis, attn_impl):
        """[b, h_local, s, hd] q/k/v → causal attention output, routed to the
        impl that is CORRECT for the sharding (shared by GPT-2 and Llama).

        ``sp_axis`` is whichever mesh axis the SEQUENCE is sharded over —
        the legacy ``sp`` ring or the ``cp`` context-parallel axis
        (``parallel.hybrid`` passes the resolved name; the impls are
        axis-name-generic). ``"ring2"`` is the cp tentpole: bidirectional
        flash ring with causal hop skipping and the KV re-streaming backward
        (``ops.ring_attention``) — the training default on cp meshes."""
        if attn_impl not in self._ATTN_IMPLS:
            # a typo would otherwise silently train on the ring/XLA fallback
            raise ValueError(f"unknown attn_impl {attn_impl!r}; choose from {self._ATTN_IMPLS}")
        if sp_axis and lax.axis_size(sp_axis) == 1:
            # a size-1 sequence ring means the sequence is NOT sharded: route
            # as single-chip so "flash" actually runs the Pallas kernel (the
            # truthy-name check used to send it through the n=1 XLA ring →
            # dense attention — silently benching the wrong implementation)
            sp_axis = None
        if sp_axis:
            # sequence is sharded: only ring/Ulysses see the full context.
            # Anything else (incl. "flash", a single-chip kernel) would be
            # silently-wrong block-diagonal attention — route it to ring.
            if attn_impl == "ring2":
                from dsml_tpu.ops.ring_attention import ring_attention as ring2_attention

                return ring2_attention(q, k, v, sp_axis, causal=True)
            if attn_impl == "ulysses":
                return ulysses_attention(q, k, v, sp_axis, causal=True)
            if attn_impl == "ulysses_flash":
                return ulysses_attention(q, k, v, sp_axis, causal=True, flash=True)
            if attn_impl == "ring_flash":
                from dsml_tpu.ops.flash import ring_flash_attention

                return ring_flash_attention(q, k, v, sp_axis, causal=True)
            return ring_attention(q, k, v, sp_axis, causal=True)
        if attn_impl in self._FLASH_IMPLS:
            # no sp axis → every flash variant degenerates to the
            # single-chip kernel (falling through to plain attention would
            # materialize the [seq, seq] scores the caller chose flash to
            # avoid)
            from dsml_tpu.ops.flash import flash_attention

            return flash_attention(q, k, v, causal=True)
        return attention(q, k, v, causal=True)

    def _flash_packs(self, sp_axis, attn_impl, n_head_local):
        """True where :meth:`_route_attention` would run the single-chip
        flash kernels AND they can index this shard's heads out of the
        projections' own ``[b, s, heads·hd]`` layout (``ops.flash.
        flash_packs``: a rule on shapes). The caller then hands the
        projections over as they are and no head-major copy is made; any
        other case (a sharded sequence, another impl, heads that are not
        64 wide in pairs) takes the head-major route as ever."""
        from dsml_tpu.ops.flash import flash_packs

        unsharded = not sp_axis or lax.axis_size(sp_axis) == 1
        return unsharded and attn_impl in self._FLASH_IMPLS and flash_packs(n_head_local, head_dim(self.config))

    def _attn_block(self, layer, h, n_head_local, tp_axis, sp_axis, attn_impl):
        x = _layer_norm(h, **layer["ln_1"])
        w = layer["attn"]["wqkv"]
        # a plain [d, 3, d_local] weight: a quantized codec (serving) keeps qmatmul's own route
        if getattr(w, "ndim", 0) == 3 and self._flash_packs(sp_axis, attn_impl, n_head_local):
            from dsml_tpu.ops.flash import flash_attention_packed

            # [b, s, 3·d_local]: q, k, v side by side, read where they lie. The slot
            # axis is folded on the WEIGHT (folding the projection's output would
            # relay it out), from its three slot slices: a reshape reads the padded
            # parameter and relays weight, gradient and both adam moments
            w = jnp.concatenate([w[:, 0], w[:, 1], w[:, 2]], axis=1)
            qkv = qmatmul(x, w, x.dtype) + layer["attn"]["bqkv"].reshape(-1)
            out, _ = flash_attention_packed(qkv, w.shape[1] // 3 // n_head_local)
        else:
            q, k, v = self._qkv_heads(layer, x, n_head_local)
            out = self._merge_heads(self._route_attention(q, k, v, sp_axis, attn_impl))
        out = qmatmul(out, layer["attn"]["wo"], out.dtype)  # row-parallel → partial sums
        if tp_axis:
            out = lax.psum(out, tp_axis)  # Megatron psum #1
        return out + layer["attn"]["bo"]

    def _mlp_block(self, mlp, x, tp_axis):
        hmid = jax.nn.gelu(qmatmul(x, mlp["w_in"], x.dtype) + mlp["b_in"])  # [b, s, d_ff/tp]
        out = qmatmul(hmid, mlp["w_out"], x.dtype)
        if tp_axis:
            out = lax.psum(out, tp_axis)  # Megatron psum #2
        return out + mlp["b_out"]

    def _moe_param_init(self, normal, res_std):
        """One expert layer's params — shared by every family that mounts
        the MoE block (GPT-2, Llama/Mixtral), so the layout and
        ``_moe_block``'s expectations can never drift apart."""
        cfg = self.config
        return {
            "gate": normal(cfg.d_model, cfg.n_experts),
            "w_in": normal(cfg.n_experts, cfg.d_model, cfg.d_ff),
            "b_in": jnp.zeros((cfg.n_experts, cfg.d_ff), jnp.dtype(cfg.dtype)),
            "w_out": normal(cfg.n_experts, cfg.d_ff, cfg.d_model, std=res_std),
            "b_out": jnp.zeros((cfg.n_experts, cfg.d_model), jnp.dtype(cfg.dtype)),
        }

    def _moe_specs(self, fsdp: int = 1):
        from jax.sharding import PartitionSpec as P

        cfg = self.config
        d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
        F = fsdp_spec_fn(fsdp)
        return {
            "gate": F(P(), d, E),
            "w_in": F(P("tp", None, None), E, d, ff),  # experts sharded over tp (EP)
            "b_in": F(P("tp", None), E, ff),
            "w_out": F(P("tp", None, None), E, ff, d),
            "b_out": F(P("tp", None), E, d),
        }

    def _moe_block(self, moe, x, tp_axis):
        """Top-k gated mixture of experts with experts sharded over
        ``tp_axis`` — real expert parallelism: token payloads ride
        ``all_to_all`` over the expert axis.

        Activations are replicated across tp (Megatron invariant), and the
        routing is capacity-bounded over this dp×sp shard's tokens with
        overflow dropped, static shapes throughout. Under EP each rank
        routes only its 1/ep token slice — gate matmul, top_k, and argsort
        all scale with T/ep (VERDICT r3 item 6) — and the GLOBAL capacity
        position of each assignment is reconstructed exactly from an
        all_gather of the per-rank [E] count vectors (rank slices are
        contiguous token-major ranges, so global position = earlier ranks'
        counts for that expert + local position). Every capacity slot
        (e, c) is therefore still owned by exactly ONE assignment, which is
        what makes the exchange exact. Routing is the sort/segment
        formulation — O(T·k) index vectors plus the [E, C, d] capacity
        buffers — NOT the dense [T, E, C] one-hot dispatch/combine tensors,
        which at Mixtral shapes (T=32k, E=8, C≈8k) would cost multi-GB per
        layer (VERDICT r2 weak #3):

        1. stable-argsort the T·k expert assignments by expert id;
        2. each assignment's position inside its expert's capacity buffer =
           its sorted index minus the expert's segment start (exclusive
           prefix over ``bincount``) — identical priority order (flattened
           token-major) to the cumsum-of-one-hots it replaces;
        3. dispatch = scatter-add of token vectors into the flat [E·C, d]
           buffer (dropped/overflow assignments scatter to a dummy row);
        4. combine = gather each assignment's expert output back from the
           buffer and weighted-sum the k assignments per token.

        Under EP, each rank scatters only its 1/ep token slice,
        ``all_to_all`` ships the slot payloads to the rank owning each
        expert shard (disjoint slots → summing the received blocks
        reconstructs the buffers exactly), the resident experts run, and a
        second ``all_to_all`` + token ``all_gather`` route the combined
        outputs back to replication (the standard MoE dispatch/return
        pair). The dispatch hop carries the capacity buffers
        (≈ top_k·capacity_factor·T·d/ep per rank).

        Values equal the single-device forward up to f32 reduction order
        (tests pin loss AND gradient parity) — with the caveat that
        routing/capacity are computed per dp×sp token shard, so drop
        patterns under capacity overflow differ from a global-batch
        dispatch (standard local-group MoE semantics).

        Falls back to replicated dispatch + psum when the token count
        doesn't split over ep (warned at trace time — the fallback loses
        the a2a bandwidth saving but not correctness)."""
        cfg = self.config
        b, s, d = x.shape
        n_exp = cfg.n_experts
        k = cfg.expert_top_k
        ep = lax.axis_size(tp_axis) if tp_axis else 1
        exp_local = n_exp // ep
        if exp_local * ep != n_exp:
            raise ValueError(f"n_experts={n_exp} not divisible by tp={ep}")
        tokens = x.reshape(-1, d)  # [T, d]
        t = tokens.shape[0]
        capacity = int(cfg.capacity_factor * t * k / n_exp) + 1
        n_assign = t * k
        n_slots = n_exp * capacity

        def route(toks):
            """Sort/segment routing over ``toks`` [t', d] → (top_p [t', k],
            flat_e [t'·k], pos [t'·k], counts [E]). ``pos`` is each
            assignment's position within its expert's segment counting only
            THESE assignments; stable sort keeps the flattened (token-major)
            order within each expert, so priority under overflow matches
            the dense cumsum formulation exactly."""
            gate_logits = toks @ moe["gate"].astype(toks.dtype)
            gate_probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
            top_p, top_e = lax.top_k(gate_probs, k)
            top_p = (top_p / top_p.sum(-1, keepdims=True)).astype(x.dtype)
            flat_e = top_e.reshape(-1)
            n = flat_e.shape[0]
            order = jnp.argsort(flat_e, stable=True)
            counts = jnp.zeros(n_exp, jnp.int32).at[flat_e].add(1)
            starts = jnp.cumsum(counts) - counts  # exclusive prefix
            pos_sorted = jnp.arange(n, dtype=jnp.int32) - starts[flat_e[order]]
            inv = jnp.zeros_like(order).at[order].set(jnp.arange(n))
            return top_p, flat_e, pos_sorted[inv], counts

        def scatter_tokens(slot, tok_idx, toks, n_rows):
            """Flat [n_rows, d] capacity buffer: scatter-add ``toks[tok_idx]``
            into ``slot``; slot ``n_rows`` is the dummy row dropped
            assignments land in."""
            buf = jnp.zeros((n_rows + 1, d), tokens.dtype)
            return buf.at[slot].add(toks[tok_idx])[:-1]

        use_a2a = ep > 1 and t % ep == 0
        if ep > 1 and not use_a2a:
            import warnings

            warnings.warn(
                f"MoE a2a dispatch disabled: {t} tokens per rank do not split "
                f"over ep={ep}; falling back to replicated dispatch + psum "
                "(correct, but pays replicated expert FLOPs and a psum instead "
                "of the all_to_all payload exchange)",
                stacklevel=2,
            )
        r = lax.axis_index(tp_axis) if ep > 1 else 0
        if use_a2a:
            from dsml_tpu.ops.collectives import all_gather, all_to_all

            # routing runs on this rank's 1/ep token slice ONLY (VERDICT r3
            # item 6: the gate matmul, top_k, and argsort all scale with
            # T/ep, not T). Global capacity positions are reconstructed from
            # the per-rank, per-expert counts: rank slices are contiguous
            # token-major ranges, so an assignment's global position within
            # its expert = (assignments to that expert on earlier ranks)
            # + its local position — an all_gather of the tiny [E] count
            # vector replaces the replicated full-T sort.
            t_local = t // ep
            n_loc = t_local * k
            tok_r = lax.dynamic_slice_in_dim(tokens, r * t_local, t_local, axis=0)
            top_p_r, flat_e_r, pos_loc, counts_r = route(tok_r)
            counts_all = all_gather(counts_r, tp_axis, axis=0, tiled=False)  # [ep, E]
            rank_base = jnp.cumsum(counts_all, axis=0) - counts_all  # exclusive
            base_r = lax.dynamic_index_in_dim(rank_base, r, 0, keepdims=False)
            pos_r = pos_loc + base_r[flat_e_r]  # global capacity position
            kept_r = pos_r < capacity
            partial = scatter_tokens(
                jnp.where(kept_r, flat_e_r * capacity + pos_r, n_slots),
                jnp.arange(n_loc, dtype=jnp.int32) // k,
                tok_r,
                n_slots,
            ).reshape(n_exp, capacity, d)
            # all_to_all over experts: send [E_local, C, d] blocks, receive
            # the ep partials for OUR experts concatenated on the capacity
            # axis; slots are disjoint so the sum is the exact buffer
            recv = all_to_all(partial, tp_axis, split_axis=0, concat_axis=1)
            expert_in = recv.reshape(exp_local, ep, capacity, d).sum(axis=1)
            # the return path combines every token's assignments on the
            # expert-owner rank, so the global index/weight vectors are
            # reconstructed by all_gathering the per-rank slices — ~12
            # bytes per assignment, vs the d-wide payloads the a2a carries
            flat_e = all_gather(flat_e_r, tp_axis, axis=0, tiled=True)  # [N]
            pos_flat = all_gather(pos_r, tp_axis, axis=0, tiled=True)
            top_p = all_gather(top_p_r, tp_axis, axis=0, tiled=True)  # [T, k]
            kept = pos_flat < capacity
            is_local_e = (flat_e // exp_local) == r
            local_slot = jnp.where(
                kept & is_local_e,
                (flat_e - r * exp_local) * capacity + pos_flat,
                exp_local * capacity,
            )
        else:
            # single-device or non-a2a fallback: full-T routing on every rank
            top_p, flat_e, pos_flat, _ = route(tokens)
            flat_tok = jnp.arange(n_assign, dtype=jnp.int32) // k  # owning token
            kept = pos_flat < capacity
            slot_flat = jnp.where(kept, flat_e * capacity + pos_flat, n_slots)
            if ep > 1:
                # slot within this rank's expert shard for each assignment
                # whose expert the shard owns (experts are contiguous blocks
                # of exp_local); everyone else lands in the dummy row
                is_local_e = (flat_e // exp_local) == r
                local_slot = jnp.where(
                    kept & is_local_e,
                    (flat_e - r * exp_local) * capacity + pos_flat,
                    exp_local * capacity,
                )
                expert_in = scatter_tokens(
                    local_slot, flat_tok, tokens, exp_local * capacity
                ).reshape(exp_local, capacity, d)
            else:
                expert_in = scatter_tokens(slot_flat, flat_tok, tokens, n_slots).reshape(
                    n_exp, capacity, d
                )

        hmid = jax.nn.gelu(
            jnp.einsum("ecd,edf->ecf", expert_in, moe["w_in"]) + moe["b_in"][:, None, :]
        )
        expert_out = jnp.einsum("ecf,efd->ecd", hmid, moe["w_out"]) + moe["b_out"][:, None, :]

        def combine_from(buf_flat, slot):
            """[T, d] weighted sum of each token's k assignment outputs,
            gathered from the flat buffer (+1 dummy zero row)."""
            buf = jnp.concatenate([buf_flat, jnp.zeros((1, d), buf_flat.dtype)])
            gathered = buf[slot].reshape(t, k, d)
            return jnp.einsum("tkd,tk->td", gathered, top_p)

        if use_a2a:
            # return path: each expert-owner combines ITS resident experts'
            # outputs for every token (non-local assignments hit the dummy
            # zero row), then a SECOND all_to_all routes each token slice's
            # partials to its owner rank — the standard MoE return — and a
            # token all_gather restores replication. ~2·T·d bytes moved,
            # matching the psum it replaces.
            partial_out = combine_from(
                expert_out.reshape(exp_local * capacity, d), local_slot
            )  # [T, d], zero outside local experts
            recv = all_to_all(
                partial_out.reshape(ep, t_local, d), tp_axis, split_axis=0, concat_axis=0
            )  # [ep, T_local, d]: block i = rank i's partial for OUR tokens
            out_r = recv.sum(axis=0)  # [T_local, d]
            out = all_gather(out_r, tp_axis, axis=0, tiled=True)  # [T, d] replicated
        elif ep > 1:
            out = lax.psum(
                combine_from(expert_out.reshape(exp_local * capacity, d), local_slot),
                tp_axis,
            )
        else:
            out = combine_from(expert_out.reshape(n_slots, d), slot_flat)
        return out.reshape(b, s, d)

    # ---- loss ------------------------------------------------------------------

    def loss_spmd(
        self,
        params: dict,
        tokens: jax.Array,
        targets: jax.Array,
        tp_axis: str | None = None,
        sp_axis: str | None = None,
        attn_impl: str = "ring",
        pp_axis: str | None = None,
        n_micro: int = 1,
    ) -> jax.Array:
        """Mean next-token cross-entropy with vocab-sharded logits: the full
        [.., vocab] row never exists on one chip — logsumexp and the target
        logit are combined across the tp axis.

        Under pipeline parallelism the head runs on replicated pipeline
        outputs, but the loss is masked to the LAST stage and ``psum``-ed over
        pp — so head/final-norm gradients land on exactly one rank (and the
        embedding's on rank 0 via the pipeline feed mask), letting the caller
        reconstruct full non-layer grads with one psum over pp
        (``parallel.hybrid``)."""
        h_raw = self._blocks_spmd(
            params, tokens, tp_axis, sp_axis, attn_impl, pp_axis=pp_axis, n_micro=n_micro
        )
        # tp of size 1 (the hybrid step always has a tp axis, often unit —
        # e.g. GPT-2-small pure-DP) is an UNsharded vocab: _head_loss_spmd
        # routes it to the chunked/dense single-shard path, not TP logits
        loss = self._head_loss_spmd(params, h_raw, targets, tp_axis)
        if pp_axis:
            is_last = lax.axis_index(pp_axis) == lax.axis_size(pp_axis) - 1
            loss = lax.psum(jnp.where(is_last, loss, 0.0), pp_axis)
        return loss

    def train_grads_1f1b_spmd(
        self,
        params: dict,
        tokens: jax.Array,
        targets: jax.Array,
        tp_axis: str | None = None,
        sp_axis: str | None = None,
        attn_impl: str = "ring",
        pp_axis: str = "pp",
        n_micro: int = 1,
        batch_axes: tuple = ("dp", "sp"),
    ):
        """Per-rank (loss, grads) via the hand-interleaved 1F1B pipeline
        schedule (``parallel.pp.pipeline_train_1f1b``) — must run under
        ``shard_map(check_vma=True)``.

        Grads come back already reduced to each leaf's replication (the
        schedule's internal-psum semantics; the head seed carries the
        1/(M·n_dp·n_sp) normalization of the global-mean loss), so the
        caller uses them as-is. The returned loss is nonzero on the LAST
        pp rank only: reduce with psum over pp + pmean over the batch axes.

        The embedding runs (replicated) outside the schedule under its own
        VJP; its cotangent is stage 0's input cotangent (``d_micros``),
        psummed over pp (rank-0 masked) and tp (per-rank partials of the
        tp-replicated residual stream) before the pullback."""
        from dsml_tpu.parallel.pp import pipeline_train_1f1b

        b = tokens.shape[0]
        if b % n_micro:
            raise ValueError(f"per-rank batch {b} not divisible by n_micro={n_micro}")
        block = self._block_closure(tp_axis, sp_axis, attn_impl)
        head_params = {k: v for k, v in params.items() if k != "layers"}

        h, embed_vjp = jax.vjp(
            lambda hp: self._embed_spmd(hp, tokens, tp_axis, sp_axis), head_params
        )
        micros = h.reshape(n_micro, b // n_micro, *h.shape[1:])
        tgt_micros = targets.reshape(n_micro, b // n_micro, *targets.shape[1:])
        vary_axes = tuple(
            dict.fromkeys(a for a in (pp_axis, *batch_axes, tp_axis, sp_axis) if a is not None)
        )
        batch_ranks = 1
        for a in batch_axes:
            batch_ranks *= lax.axis_size(a)
        def stage_fn(stage_layers, x):
            def body(hh, one_layer):
                return block(one_layer, hh), None

            out, _ = lax.scan(body, x, stage_layers)
            return out

        def head_fn(hp, y, tgt):
            return self._head_loss_spmd(hp, y, tgt, tp_axis)

        loss, d_stage, d_head, d_micros = pipeline_train_1f1b(
            stage_fn, head_fn, params["layers"], head_params, micros, tgt_micros,
            pp_axis, vary_axes=vary_axes, loss_seed_scale=1.0 / (n_micro * batch_ranks),
        )
        # cotangent of the (pp/tp-replicated) embedded stream: rank 0
        # holds the pipeline's feed cotangent, tp ranks hold partials
        sum_axes = (pp_axis,) + ((tp_axis,) if tp_axis else ())
        d_h = lax.psum(d_micros.reshape(b, *h.shape[1:]), sum_axes)
        (d_embed,) = embed_vjp(d_h)
        grads_head = jax.tree.map(jnp.add, d_head, d_embed)
        return loss, {**grads_head, "layers": d_stage}

    # ---- single-device conveniences (parity + Trainer protocol) ----------------

    def apply(self, params: dict, tokens: jax.Array) -> jax.Array:
        return self.apply_spmd(params, tokens)

    def loss(self, params: dict, tokens: jax.Array, targets: jax.Array) -> jax.Array:
        return self.loss_spmd(params, tokens, targets)

    # ---- autoregressive decoding (KV cache) ------------------------------------
    # The reference has no inference path at all; a serving-shaped decode loop
    # is table stakes for a framework. Static shapes throughout: the cache is
    # pre-allocated at max_seq and positions are masked, so prefill + every
    # decode step are fixed-shape XLA programs (one compile each).

    def init_cache(self, batch: int, tp_size: int = 1) -> list:
        """KV cache, pre-allocated at max_seq. Under TP the cache holds only
        this rank's head shard — attention is head-parallel, so decode's
        per-chip cache memory drops by tp (the point of sharded serving).
        With ``config.kv_quant`` the entries are int8 + per-position scales
        (see :meth:`_cache_write`)."""
        cfg = self.config
        if cfg.n_head % tp_size:
            raise ValueError(f"n_head={cfg.n_head} not divisible by tp={tp_size}")
        return [
            self._cache_entry(batch, cfg.n_head // tp_size)
            for _ in range(cfg.n_layer)
        ]

    def _kv_mode(self) -> str | None:
        """None | "int8" | "int4" — the normalized ``config.kv_quant``
        (True is "int8" for back-compat). Unknown strings fail loudly
        rather than silently serving an unquantized cache."""
        kq = self.config.kv_quant
        if not kq:
            return None
        if kq is True or kq == "int8":
            return "int8"
        if kq == "int4":
            return "int4"
        raise ValueError(
            f"unknown kv_quant mode {kq!r}; choose False, True/'int8', or 'int4'"
        )

    def _cache_entry(self, batch: int, n_heads: int) -> dict:
        cfg = self.config
        hd = head_dim(cfg)
        mode = self._kv_mode()
        if mode:
            if mode == "int4":
                if hd % 2:
                    raise ValueError(f"kv_quant='int4' needs an even head_dim, got {hd}")
                shape = (batch, n_heads, cfg.max_seq, hd // 2)  # 2 nibbles/byte
                dt = jnp.uint8
            else:
                shape = (batch, n_heads, cfg.max_seq, hd)
                dt = jnp.int8
            return {
                "k": jnp.zeros(shape, dt),
                "k_s": jnp.zeros((*shape[:3], 1), jnp.float32),
                "v": jnp.zeros(shape, dt),
                "v_s": jnp.zeros((*shape[:3], 1), jnp.float32),
            }
        dt = jnp.dtype(cfg.dtype)
        shape = (batch, n_heads, cfg.max_seq, hd)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def _kv_quantize(self, x, mode: str | None = None):
        """[b, h, s, hd] → (quantized values, f32 scale [b, h, s, 1]):
        symmetric absmax per position — each token's K/V row quantizes
        independently, so cache writes never touch other rows' scales.
        Delegates to ``ops.quantization.quantize_kv_rows`` — THE one KV
        codec (int4 packs channel halves contiguously via the shared
        ``pack_int4`` nibble format the collective wire path uses too),
        so the dense cache and the serving page pool produce identical
        bytes per row (the page-table gather parity rests on it)."""
        from dsml_tpu.ops.quantization import quantize_kv_rows

        return quantize_kv_rows(x, mode or self._kv_mode())

    def _cache_write(self, c: dict, kc, vc, write) -> dict:
        """Write new K/V rows through ``write(cache_array, new_rows)`` —
        the ONE place the quantized and plain layouts branch. ``write`` is
        the caller's placement (full-prefix ``dynamic_update_slice``, shared
        decode position, or the per-slot batched scatter); scale tensors ride
        the same placement with their trailing dim of 1 (int4's packed
        values ride it with trailing dim hd/2)."""
        if self._kv_mode():
            kq, ks = self._kv_quantize(kc)
            vq, vs = self._kv_quantize(vc)
            return {"k": write(c["k"], kq), "k_s": write(c["k_s"], ks),
                    "v": write(c["v"], vq), "v_s": write(c["v_s"], vs)}
        return {"k": write(c["k"], kc), "v": write(c["v"], vc)}

    @staticmethod
    def _unpack_int4(p):
        """[..., hd/2] packed nibbles → [..., hd] int8 in [-7, 7] (channel
        halves are contiguous — see :meth:`_kv_quantize`; the shared
        ``ops.quantization.unpack_int4``, a concat of two elementwise ops,
        not an interleaving gather)."""
        from dsml_tpu.ops.quantization import unpack_int4

        return unpack_int4(p)

    def _cache_attn_inputs(self, c: dict):
        """(ck, cv, k_s, v_s) for :meth:`_decode_attention` — scales are
        None for the plain cache. The int8 values go INTO the attention
        dots as-is (the int8→float convert feeds the dot operand, which XLA
        fuses, instead of materializing a dequantized full-width cache
        copy); the per-position scales, constant along ``hd``, fold in
        AFTER each dot — mathematically identical to dequantize-then-dot.
        int4 unpacks its nibbles to the same int8 form first (fused the
        same way — the packed cache is what HBM traffic pays for)."""
        mode = self._kv_mode()
        if mode == "int4":
            return (self._unpack_int4(c["k"]), self._unpack_int4(c["v"]),
                    c["k_s"], c["v_s"])
        if mode:
            return c["k"], c["v"], c["k_s"], c["v_s"]
        return c["k"], c["v"], None, None

    def _qkv(self, layer, x):
        """Fused QKV projection, ``[b, s, 3, d(/tp)]``. ``layer['attn']['wqkv']``
        is [d, 3, d(/tp)] — the slot axis separates q/k/v so a TP shard of the
        last dim is purely a head split."""
        return qmatmul(x, layer["attn"]["wqkv"], x.dtype) + layer["attn"]["bqkv"]

    def _qkv_heads(self, layer, x, n_head_local: int | None = None):
        """:meth:`_qkv` + head split, head-major. ``n_head_local`` is the head
        count actually present in this shard (full ``n_head`` when unsharded)."""
        n_head_local = n_head_local or self.config.n_head
        qkv = self._qkv(layer, x)

        def heads(t):  # [b, s, d_local] -> [b, h_local, s, hd]
            b, s, _ = t.shape
            return t.reshape(b, s, n_head_local, -1).transpose(0, 2, 1, 3)

        return heads(qkv[:, :, 0]), heads(qkv[:, :, 1]), heads(qkv[:, :, 2])

    def _merge_heads(self, t):  # [b, H, s, hd] -> [b, s, d]
        b, _, s, _ = t.shape
        return t.transpose(0, 2, 1, 3).reshape(b, s, -1)

    def _final_norm(self, params, h):
        """Pre-head normalization hook (Llama: RMSNorm over rms_f)."""
        return _layer_norm(h, **params["ln_f"])

    def _unembed_matrix(self, params):
        """[vocab(/tp), d] unembedding hook — GPT-2 ties it to wte; Llama
        overrides with the untied lm_head."""
        return params["wte"]

    def _ffn(self, layer, h, tp_axis=None):
        if self.config.n_experts:
            return h + self._moe_block(layer["moe"], _layer_norm(h, **layer["ln_2"]), tp_axis)
        return h + self._mlp_block(layer["mlp"], _layer_norm(h, **layer["ln_2"]), tp_axis)

    def _unembed_full(self, params, h, tp_axis):
        """h [..., d] → FULL-vocab logits. Under TP the unembedding is
        vocab-sharded; decode needs the whole row for sampling, so the local
        [..., vocab/tp] shards all_gather over tp (tiny at decode batch
        sizes — [batch, vocab], not [tokens, vocab])."""
        local = h @ self._unembed_matrix(params).T
        if tp_axis:
            return lax.all_gather(local, tp_axis, axis=-1, tiled=True)
        return local

    # Serving hooks — ONE prefill/decode loop serves every model family;
    # subclasses override only the architecture-specific pieces (Llama:
    # RMSNorm, RoPE'd GQA projections, grouped cache attention, no biases).

    def _norm1(self, layer, h):
        return _layer_norm(h, **layer["ln_1"])

    def _attn_out_bias(self, layer):
        return layer["attn"]["bo"]

    def _prefill_use_flash(self, t: int) -> bool:
        """Gate for the flash-kernel prefill path — separable so tests can
        force it on under the Pallas interpreter (CI has no TPU)."""
        return jax.default_backend() == "tpu" and t >= 512

    def _serving_qkv(self, layer, x, positions, tp_size):
        """(q, k_cache, v_cache, k_attn, v_attn) for the serving path.
        ``positions`` [s] are the global token positions of ``x`` (ignored
        here — GPT-2 positions live in wpe; Llama applies RoPE)."""
        q, k, v = self._qkv_heads(layer, x, self.config.n_head // tp_size)
        return q, k, v, k, v

    @staticmethod
    def _valid_to_mask(valid):
        """``valid`` → broadcastable [b?, 1(head), q?, S] mask. Accepted
        shapes: [S] (shared depth), [b, S] (per-slot depth, continuous
        batching), [b, q, S] (multi-query — chunked prefill's causal+prefix
        mask)."""
        if valid.ndim == 1:
            return valid[None, None, None, :]
        if valid.ndim == 2:
            return valid[:, None, None, :]
        return valid[:, None, :, :]

    def _decode_attention(self, q, ck, cv, valid, k_s=None, v_s=None):
        """q [b, H, q, hd] against the full cache [b, Hc, S, hd] (H == Hc
        here; Llama overrides with the grouped-query form; q=1 for decode
        steps, q=C for chunked prefill). ``valid`` is [S] (shared depth),
        [b, S] (per-slot depth, continuous batching), or [b, q, S]
        (chunked prefill). ``k_s``/``v_s`` [b, Hc, S, 1] are the int8
        cache's per-position scales, folded in after each dot (see
        ``_cache_attn_inputs``)."""
        vmask = self._valid_to_mask(valid)
        if k_s is None:
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, ck) * (q.shape[-1] ** -0.5)
            scores = jnp.where(vmask, scores, _NEG_INF)
            return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), cv)
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q.astype(jnp.float32), ck.astype(jnp.float32)
        ) * (q.shape[-1] ** -0.5)
        scores = scores * jnp.swapaxes(k_s, -1, -2)  # fold key scales: [b, h, 1, S]
        scores = jnp.where(vmask, scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1) * jnp.swapaxes(v_s, -1, -2)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, cv.astype(jnp.float32)).astype(q.dtype)

    def prefill(
        self,
        params: dict,
        tokens: jax.Array,
        tp_axis: str | None = None,
        last_index=None,
    ):
        """Run the prompt [batch, T] in ONE pass, filling the cache.
        Returns (last-position logits [batch, vocab], cache).

        ``last_index`` (static or traced int) reads the logits at that
        position instead of T-1 — the bucketed-prefill hook: a prompt of
        true length L right-padded to a compiled bucket length passes
        ``last_index=L-1`` (causality keeps positions < L pad-free; pad
        rows land in the cache beyond L but the decode mask never admits
        them before they're overwritten).

        With ``tp_axis`` (call under shard_map with Megatron-sharded
        params), the pass is head-parallel: local-head attention + one psum
        per block pair, vocab-sharded embed/unembed, per-rank cache shard."""
        b, t = tokens.shape
        tp_size = lax.axis_size(tp_axis) if tp_axis else 1
        positions = jnp.arange(t, dtype=jnp.int32)
        h = self._embed_spmd(params, tokens, tp_axis)
        cache = self.init_cache(b, tp_size)
        # long prompts: the plain path materializes [T, T] scores per head —
        # route through the flash kernel so prefill memory stays O(block²)
        # (untileable lengths ride the kernel's padded kv_stop path)
        use_flash = self._prefill_use_flash(t)
        if use_flash:
            from dsml_tpu.ops.flash import flash_attention

        for i, layer in enumerate(params["layers"]):
            x = self._norm1(layer, h)
            q, kc, vc, ka, va = self._serving_qkv(layer, x, positions, tp_size)
            out = (
                flash_attention(q, ka, va, causal=True)
                if use_flash
                else attention(q, ka, va, causal=True)
            )
            attn_out = qmatmul(self._merge_heads(out), layer["attn"]["wo"], h.dtype)
            if tp_axis:
                attn_out = lax.psum(attn_out, tp_axis)
            h = h + attn_out + self._attn_out_bias(layer)
            h = self._ffn(layer, h, tp_axis)
            cache[i] = self._cache_write(
                cache[i], kc, vc,
                lambda arr, new: lax.dynamic_update_slice(
                    arr, new, (0,) * arr.ndim
                ),
            )
        h = self._final_norm(params, h)
        if last_index is None:
            h_last = h[:, -1]
        else:
            h_last = lax.dynamic_index_in_dim(
                h, jnp.asarray(last_index, jnp.int32), axis=1, keepdims=False
            )
        return self._unembed_full(params, h_last, tp_axis), cache

    def _decode_core(self, params, cache, h, positions, valid, write, tp_axis,
                     read_index=None):
        """The shared decode layer loop: norm → qkv → cache write (via the
        caller's ``write`` placement) → cached attention → wo/psum → ffn,
        then final-norm + full-vocab unembed. ``decode_step`` (shared
        scalar position) and ``decode_step_slots`` (per-slot position
        vector) differ ONLY in positions/valid/write; ``prefill_chunk``
        additionally passes ``read_index`` (the chunk-local position whose
        logits to return — decode's single query reads index 0), and
        ``verify_step`` passes ``read_index="all"`` for per-position
        logits [b, C, vocab]."""
        tp_size = lax.axis_size(tp_axis) if tp_axis else 1
        new_cache = []
        for layer, c in zip(params["layers"], cache):
            x = self._norm1(layer, h)
            q, kc, vc, _, _ = self._serving_qkv(layer, x, positions, tp_size)
            c = self._cache_write(c, kc, vc, write)
            ck, cv, k_s, v_s = self._cache_attn_inputs(c)
            out = self._decode_attention(q, ck, cv, valid, k_s, v_s)
            attn_out = qmatmul(self._merge_heads(out), layer["attn"]["wo"], h.dtype)
            if tp_axis:
                attn_out = lax.psum(attn_out, tp_axis)
            h = h + attn_out + self._attn_out_bias(layer)
            h = self._ffn(layer, h, tp_axis)
            new_cache.append(c)
        h = self._final_norm(params, h)
        if isinstance(read_index, str) and read_index == "all":
            h_last = h  # [b, C, d] → logits at every query position
        elif read_index is None:
            h_last = h[:, 0]
        else:
            h_last = lax.dynamic_index_in_dim(
                h, jnp.asarray(read_index, jnp.int32), axis=1, keepdims=False
            )
        return self._unembed_full(params, h_last, tp_axis), new_cache

    def decode_step(
        self, params: dict, cache: list, tokens: jax.Array, pos: jax.Array,
        tp_axis: str | None = None,
    ):
        """One decode step: ``tokens`` [batch] at position ``pos`` (scalar,
        int or traced). Returns (logits [batch, vocab], updated cache)."""
        cfg = self.config
        positions = jnp.reshape(jnp.asarray(pos, jnp.int32), (1,))
        h = self._embed_spmd(params, tokens[:, None], tp_axis, seq_offset=pos)
        valid = jnp.arange(cfg.max_seq) <= pos  # attend to cache[0..pos]
        return self._decode_core(
            params, cache, h, positions, valid,
            lambda arr, new: lax.dynamic_update_slice(arr, new, (0, 0, pos, 0)),
            tp_axis,
        )

    def decode_step_slots(
        self, params: dict, cache: list, tokens: jax.Array, pos: jax.Array,
        tp_axis: str | None = None,
    ):
        """One decode step with PER-SLOT positions — the continuous-batching
        kernel (``dsml_tpu.serving``): ``tokens`` [batch] are each slot's
        last token, ``pos`` [batch] each slot's own depth. Shapes are fully
        static; per-slot cache writes are a batched scatter at
        ``(b, :, pos[b], :)`` and the attention mask admits ``s <= pos[b]``
        per row, so slots at different depths decode in ONE program.
        Returns (logits [batch, vocab], updated cache)."""
        cfg = self.config
        b = tokens.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        positions = pos[:, None]  # [b, 1]: per-row position of the 1 new token
        h = self._embed_spmd(params, tokens[:, None], tp_axis, seq_offset=positions)
        valid = jnp.arange(cfg.max_seq)[None, :] <= pos[:, None]  # [b, S]
        bidx = jnp.arange(b)
        return self._decode_core(
            params, cache, h, positions, valid,
            lambda arr, new: arr.at[bidx, :, pos, :].set(new[:, :, 0, :]),
            tp_axis,
        )

    def verify_step(
        self, params: dict, cache: list, tokens: jax.Array, start,
        tp_axis: str | None = None,
    ):
        """Multi-query decode for SPECULATIVE verification: ``tokens``
        [b, C] (each row: its last accepted token followed by C−1 draft
        tokens) run at per-row positions ``start[b]..start[b]+C-1``
        against the cache, writing their K/V rows and returning logits at
        EVERY position — (logits [b, C, vocab], cache).

        One call scores all C candidate continuations of every row (the
        verify half of speculative decoding — ``models.speculative``);
        rows sit at independent depths, so the write is a per-row
        ``dynamic_update_slice`` (vmapped → batched scatter) and the mask
        admits ``s <= start[b]+i`` per query. Rejected drafts leave
        garbage K/V rows beyond the accepted prefix; the NEXT verify
        window starts at the first garbage row and is at least as long,
        so every garbage row is overwritten before any query can attend
        to it (same argument as bucketed prefill's pad rows)."""
        cfg = self.config
        _, c = tokens.shape
        start = jnp.asarray(start, jnp.int32)  # [b]
        positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)  # [b, C]
        h = self._embed_spmd(params, tokens, tp_axis, seq_offset=start[:, None])
        valid = (
            jnp.arange(cfg.max_seq)[None, None, :] <= positions[:, :, None]
        )  # [b, C, S]

        def write(arr, new):  # arr [b, H, S, x], new [b, H, C, x]
            return jax.vmap(
                lambda a, nw, p: lax.dynamic_update_slice(a, nw, (0, p, 0))
            )(arr, new, start)

        return self._decode_core(
            params, cache, h, positions, valid, write, tp_axis, read_index="all"
        )

    def prefill_chunk(
        self, params: dict, cache: list, tokens: jax.Array, start,
        tp_axis: str | None = None, last_index=None,
    ):
        """One CHUNK of a chunked prefill: run ``tokens`` [b, C] at global
        positions ``start..start+C-1`` against a cache whose rows < start
        are already filled, writing this chunk's K/V rows at
        [start, start+C). Returns (logits [b, vocab] read at chunk-LOCAL
        ``last_index`` — default C-1 — and the updated cache).

        Chaining ceil(L/C) chunks over a prompt reproduces :meth:`prefill`
        (pinned in tests): each chunk's queries attend to the cached prefix
        plus causally to the chunk itself. This is the Orca/vLLM
        chunked-prefill schedule shape — the continuous batcher runs decode
        quanta BETWEEN a long admission's chunks instead of stalling every
        active slot for the whole prompt (``dsml_tpu.serving``).

        ``start`` and ``last_index`` may be traced: one compile serves every
        chunk. ``start + C`` must not exceed ``max_seq`` (the caller pads
        the final partial chunk; pad rows land in the cache beyond the true
        length, where the decode mask never admits them before they are
        overwritten — the same argument as bucketed prefill). With
        ``config.kv_quant`` the within-prompt attention reads int8 cache
        rows, whereas whole-prompt prefill attends exactly — the standard
        chunked-prefill approximation, documented at the serving layer."""
        cfg = self.config
        _, c = tokens.shape
        start = jnp.asarray(start, jnp.int32)
        positions = start + jnp.arange(c, dtype=jnp.int32)  # [C] global
        h = self._embed_spmd(params, tokens, tp_axis, seq_offset=start)
        # query i (global position start+i) sees cache rows s <= start+i:
        # the already-filled prefix plus the chunk's own causal triangle
        valid = (
            jnp.arange(cfg.max_seq)[None, None, :] <= positions[None, :, None]
        )  # [1, C, S] — broadcasts over batch
        return self._decode_core(
            params, cache, h, positions, valid,
            lambda arr, new: lax.dynamic_update_slice(arr, new, (0, 0, start, 0)),
            tp_axis,
            read_index=c - 1 if last_index is None else last_index,
        )

    # ---- paged KV cache (the serving page pool) --------------------------------
    # The dense cache above pre-allocates max_seq rows PER SLOT; the paged
    # variants below read/write a shared POOL of fixed-size token pages
    # through a per-slot page table, so a worker's HBM pays for the rows
    # requests actually hold (int4-quantized by default) instead of
    # n_slots × max_seq dense rows — the concurrent-sequence capacity
    # lever (``dsml_tpu.serving.batcher`` owns the allocator/CoW logic;
    # docs/SERVING.md § Paged KV). Same layer loop, same attention, same
    # sampling surfaces: only the cache placement (scatter at
    # (physical page, row)) and the attention read (page-table gather)
    # differ, which is what keeps paged tokens bit-identical to the
    # dense quantized cache's (pinned in tests).

    @staticmethod
    def _page_mode(quant) -> str | None:
        """None | "int8" | "int4" — normalized page-pool quantization
        (the paged analog of :meth:`_kv_mode`, but per-call: a serving
        pool's codec is a deployment choice, not a model-config one)."""
        if not quant:
            return None
        if quant is True or quant == "int4":
            return "int4"
        if quant == "int8":
            return "int8"
        raise ValueError(
            f"unknown page quant mode {quant!r}; choose False, 'int8', or "
            "True/'int4'"
        )

    def init_page_pool(self, n_pages: int, page_size: int, tp_size: int = 1,
                       quant="int4") -> list:
        """Per-layer page pool: ``n_pages`` physical pages of ``page_size``
        token rows each, shared by every slot through a page table.
        ``page_size`` must divide ``max_seq`` (a slot's table then has
        exactly ``max_seq // page_size`` entries and the gathered view is
        shape-identical to the dense cache). Page 0 is the caller's
        SCRATCH page by convention: free/retired slots point every table
        entry at it, so their (masked, never-read) writes can't land in
        another slot's pages."""
        cfg = self.config
        if cfg.n_head % tp_size:
            raise ValueError(f"n_head={cfg.n_head} not divisible by tp={tp_size}")
        if page_size < 1 or cfg.max_seq % page_size:
            raise ValueError(
                f"page_size must divide max_seq={cfg.max_seq}, got {page_size}"
            )
        if n_pages < 2:
            raise ValueError(
                f"need n_pages >= 2 (page 0 is the scratch page), got {n_pages}"
            )
        mode = self._page_mode(quant)
        hd = head_dim(cfg)
        n_heads = getattr(cfg, "n_kv_head", cfg.n_head) // tp_size
        if mode == "int4":
            if hd % 2:
                raise ValueError(f"int4 pages need an even head_dim, got {hd}")
            shape, dt = (n_pages, n_heads, page_size, hd // 2), jnp.uint8
        elif mode == "int8":
            shape, dt = (n_pages, n_heads, page_size, hd), jnp.int8
        else:
            shape, dt = (n_pages, n_heads, page_size, hd), jnp.dtype(cfg.dtype)
        def entry():
            # fresh buffers PER LAYER: sharing one zeros array across
            # layers would hand the same buffer to the jitted programs
            # twice, which donation rejects
            e = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
            if mode:
                sshape = (n_pages, n_heads, page_size, 1)
                e.update(k_s=jnp.zeros(sshape, jnp.float32),
                         v_s=jnp.zeros(sshape, jnp.float32))
            return e

        return [entry() for _ in range(cfg.n_layer)]

    def _paged_write(self, c: dict, kc, vc, write, mode):
        """The paged analog of :meth:`_cache_write`: quantize the new K/V
        rows per the pool codec and place values + scales through the
        caller's ``write`` (a scatter at (physical page, row in page))."""
        if mode:
            kq, ks = self._kv_quantize(kc, mode)
            vq, vs = self._kv_quantize(vc, mode)
            return {"k": write(c["k"], kq), "k_s": write(c["k_s"], ks),
                    "v": write(c["v"], vq), "v_s": write(c["v_s"], vs)}
        return {"k": write(c["k"], kc), "v": write(c["v"], vc)}

    def _paged_attn_inputs(self, c: dict, page_table, mode):
        """Gather one layer's pool through ``page_table`` [b, n_pt] into
        the dense attention view ``[b, H, n_pt·page_size, ·]`` —
        :meth:`_decode_attention` then runs unchanged (the gather IS the
        paged-attention read; positions past a slot's depth land on
        whatever page the table names, page 0 for unallocated entries,
        and the validity mask never admits them)."""

        def g(arr):
            t = arr[page_table]  # [b, n_pt, H, page, x]
            b, npt, h, pg, x = t.shape
            return t.transpose(0, 2, 1, 3, 4).reshape(b, h, npt * pg, x)

        if mode == "int4":
            return (self._unpack_int4(g(c["k"])), self._unpack_int4(g(c["v"])),
                    g(c["k_s"]), g(c["v_s"]))
        if mode:
            return g(c["k"]), g(c["v"]), g(c["k_s"]), g(c["v_s"])
        return g(c["k"]), g(c["v"]), None, None

    def _decode_core_paged(self, params, pool, page_table, h, positions,
                           valid, write, tp_axis, mode, read_index=None):
        """:meth:`_decode_core` against a page pool: per layer — norm →
        qkv → quantized page write (the caller's scatter placement) →
        paged-attention read → wo/psum → ffn. The three paged serving
        surfaces (decode / chunked prefill / verify) differ only in
        positions/valid/write, exactly like their dense twins.

        The attention read routes per ``DSML_PAGED_ATTN`` (trace-time):
        the Pallas kernel walks the page table directly — one page DMA'd
        per grid step, dequantized in-kernel, folded into a running
        (out, lse) merge, dead/scratch entries skip-predicated — so the
        dense ``[b, H, S, hd]`` view is never materialized and HBM
        traffic scales with LIVE pages; the XLA gather path stays the
        off-TPU route and the parity oracle (``ops.paged_attention``). All
        three surfaces' masks are ``key_pos <= query_pos``, which is why
        one kernel serves them: ``positions`` broadcast to [b, C] IS the
        mask."""
        from dsml_tpu.ops.paged_attention import paged_attention, paged_attn_impl

        # pass the page geometry so the router can refuse a working set
        # that would blow the VMEM budget (a ValueError naming it, instead
        # of dying inside Mosaic at compile time)
        use_pallas = paged_attn_impl(
            page_size=pool[0]["k"].shape[2],
            head_dim=head_dim(self.config),
            mode=mode,
        ) == "pallas"
        b_q, c_q = h.shape[0], h.shape[1]
        posq = jnp.broadcast_to(
            jnp.atleast_2d(jnp.asarray(positions, jnp.int32)), (b_q, c_q)
        )
        tp_size = lax.axis_size(tp_axis) if tp_axis else 1
        new_pool = []
        for layer, c in zip(params["layers"], pool):
            x = self._norm1(layer, h)
            q, kc, vc, _, _ = self._serving_qkv(layer, x, positions, tp_size)
            c = self._paged_write(c, kc, vc, write, mode)
            if use_pallas:
                out = paged_attention(q, c, page_table, posq, mode)
            else:
                ck, cv, k_s, v_s = self._paged_attn_inputs(c, page_table, mode)
                out = self._decode_attention(q, ck, cv, valid, k_s, v_s)
            attn_out = qmatmul(self._merge_heads(out), layer["attn"]["wo"], h.dtype)
            if tp_axis:
                attn_out = lax.psum(attn_out, tp_axis)
            h = h + attn_out + self._attn_out_bias(layer)
            h = self._ffn(layer, h, tp_axis)
            new_pool.append(c)
        h = self._final_norm(params, h)
        if isinstance(read_index, str) and read_index == "all":
            h_last = h
        elif read_index is None:
            h_last = h[:, 0]
        else:
            h_last = lax.dynamic_index_in_dim(
                h, jnp.asarray(read_index, jnp.int32), axis=1, keepdims=False
            )
        return self._unembed_full(params, h_last, tp_axis), new_pool

    def decode_step_slots_paged(
        self, params: dict, pool: list, page_table: jax.Array,
        tokens: jax.Array, pos: jax.Array, tp_axis: str | None = None,
        quant="int4",
    ):
        """:meth:`decode_step_slots` against a page pool: ``page_table``
        [b, max_seq/page_size] names each slot's physical pages; the new
        K/V row scatters at (table[b, pos[b]//page], pos[b] % page).
        Returns (logits [b, vocab], updated pool)."""
        cfg = self.config
        b = tokens.shape[0]
        mode = self._page_mode(quant)
        pos = jnp.asarray(pos, jnp.int32)
        page_size = cfg.max_seq // page_table.shape[1]
        positions = pos[:, None]
        h = self._embed_spmd(params, tokens[:, None], tp_axis, seq_offset=positions)
        valid = jnp.arange(cfg.max_seq)[None, :] <= pos[:, None]
        bidx = jnp.arange(b)
        phys = page_table[bidx, pos // page_size]  # [b]
        row = pos % page_size

        def write(arr, new):  # arr [P, H, page, x], new [b, H, 1, x]
            return arr.at[phys, :, row, :].set(new[:, :, 0, :])

        return self._decode_core_paged(
            params, pool, page_table, h, positions, valid, write, tp_axis, mode
        )

    def prefill_chunk_paged(
        self, params: dict, pool: list, page_table: jax.Array,
        tokens: jax.Array, start, tp_axis: str | None = None,
        last_index=None, quant="int4",
    ):
        """:meth:`prefill_chunk` against a page pool: ``tokens`` [1, C] at
        global positions ``start..start+C-1`` scatter into the pages the
        1-row ``page_table`` [1, n_pt] names. Chunk chaining under a
        quantized pool is CHUNK-SIZE-INVARIANT (every query reads every
        key quantized, regardless of where chunk boundaries fall), which
        is why prefix pages registered with one chunk size match a
        prefill worker's bytes at another — pinned in tests."""
        cfg = self.config
        _, c = tokens.shape
        mode = self._page_mode(quant)
        start = jnp.asarray(start, jnp.int32)
        page_size = cfg.max_seq // page_table.shape[1]
        positions = start + jnp.arange(c, dtype=jnp.int32)  # [C] global
        h = self._embed_spmd(params, tokens, tp_axis, seq_offset=start)
        valid = (
            jnp.arange(cfg.max_seq)[None, None, :] <= positions[None, :, None]
        )  # [1, C, S]
        phys = page_table[0, positions // page_size]  # [C]
        row = positions % page_size

        def write(arr, new):  # arr [P, H, page, x], new [1, H, C, x]
            return arr.at[phys, :, row, :].set(new[0].transpose(1, 0, 2))

        return self._decode_core_paged(
            params, pool, page_table, h, positions, valid, write, tp_axis,
            mode, read_index=c - 1 if last_index is None else last_index,
        )

    def verify_step_paged(
        self, params: dict, pool: list, page_table: jax.Array,
        tokens: jax.Array, start, tp_axis: str | None = None, quant="int4",
    ):
        """:meth:`verify_step` against a page pool — the speculative
        verify window [b, C] written/read through each slot's page table.
        Rejected drafts leave garbage rows in the slot's own reserved
        pages (never shared ones — the allocator reserves decode+window
        rows privately), and the next window overwrites them before any
        query attends — the dense path's invariant, unchanged."""
        cfg = self.config
        b, c = tokens.shape
        mode = self._page_mode(quant)
        start = jnp.asarray(start, jnp.int32)  # [b]
        page_size = cfg.max_seq // page_table.shape[1]
        positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)  # [b, C]
        h = self._embed_spmd(params, tokens, tp_axis, seq_offset=start[:, None])
        valid = (
            jnp.arange(cfg.max_seq)[None, None, :] <= positions[:, :, None]
        )  # [b, C, S]
        phys = page_table[jnp.arange(b)[:, None], positions // page_size]  # [b, C]
        row = positions % page_size

        def write(arr, new):  # arr [P, H, page, x], new [b, H, C, x]
            return arr.at[phys, :, row, :].set(new.transpose(0, 2, 1, 3))

        return self._decode_core_paged(
            params, pool, page_table, h, positions, valid, write, tp_axis,
            mode, read_index="all",
        )

    def generate(
        self,
        params: dict,
        prompt: jax.Array,  # [batch, T] int32
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        seed: int = 0,
        eos_id: int | None = None,
    ) -> jax.Array:
        """Sample ``max_new_tokens`` continuations. ``temperature == 0`` is
        greedy; otherwise softmax sampling, optionally truncated to the
        ``top_k`` most likely tokens and/or the nucleus holding ``top_p``
        probability mass. Returns [batch, max_new_tokens]; with ``eos_id``
        a row that emits it keeps emitting ``eos_id`` for its remaining
        positions (shapes stay static — the pad region marks early stop,
        matching the serving batcher's per-request truncation point)."""
        t = prompt.shape[1]
        self._check_generate_args(t, max_new_tokens, temperature, top_k, top_p)
        run = self._generate_fn(t, max_new_tokens, float(temperature), int(top_k),
                                float(top_p),
                                eos_id=None if eos_id is None else int(eos_id))
        return run(params, prompt.astype(jnp.int32), jax.random.PRNGKey(seed))

    def _check_generate_args(self, t, max_new_tokens, temperature, top_k, top_p):
        cfg = self.config
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if t + max_new_tokens > cfg.max_seq:
            raise ValueError(
                f"prompt ({t}) + max_new_tokens ({max_new_tokens}) exceeds max_seq={cfg.max_seq}"
            )
        if top_k < 0 or top_k > cfg.vocab_size:
            raise ValueError(f"top_k must be in [0, vocab_size={cfg.vocab_size}], got {top_k}")
        if not 0.0 <= top_p <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got {top_p}")
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")

    def generate_spmd(
        self,
        params: dict,
        prompt: jax.Array,
        max_new_tokens: int,
        mesh,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        seed: int = 0,
        dp_shard: bool = False,
        eos_id: int | None = None,
    ) -> jax.Array:
        """TP-sharded serving: :meth:`generate` with Megatron-sharded params
        over the mesh's ``tp`` axis (``shard_params(model.param_specs())``
        placement). Head-parallel prefill/decode with a per-rank KV-cache
        shard; every rank reconstructs the full logits row (vocab-shard
        all_gather) and runs the identical sampler with the identical key,
        so the tokens match the single-device path exactly (tests pin it).
        The reference has no inference at all — this is the serving shape a
        125M+ flagship needs.

        ``dp_shard=True`` additionally shards the BATCH over the mesh's
        ``dp`` axis — throughput serving: each dp group decodes its own
        prompt rows, tp still shards heads within the group. Sampler keys
        fold in the GLOBAL row index, so results are independent of how the
        batch is split (dp=N equals dp=1, both with ``dp_shard=True``);
        greedy decoding additionally equals :meth:`generate`. Sampled runs
        use a different key-per-row derivation than the shared-key unsharded
        paths, so they are row-decomposable rather than bit-identical to
        ``dp_shard=False``."""
        b, t = prompt.shape
        self._check_generate_args(t, max_new_tokens, temperature, top_k, top_p)
        tp_size = mesh.shape.get("tp", 1)
        if self.config.n_head % tp_size:
            raise ValueError(f"n_head={self.config.n_head} not divisible by tp={tp_size}")
        from jax.sharding import PartitionSpec as P

        dp_size = mesh.shape.get("dp", 1) if dp_shard else 1
        if dp_shard and b % dp_size:
            raise ValueError(f"batch {b} not divisible by dp={dp_size} for dp_shard")
        batch_spec = P("dp") if dp_shard else P()
        eos_id = None if eos_id is None else int(eos_id)  # stable cache key
        key_ = ("spmd", mesh, t, max_new_tokens, float(temperature), int(top_k),
                float(top_p), dp_shard, eos_id)
        cache = self._gen_cache_dict()
        run = cache.get(key_)
        if run is None:
            raw = self._generate_fn(
                t, max_new_tokens, float(temperature), int(top_k), float(top_p),
                tp_axis="tp", jit=False, dp_axis="dp" if dp_shard else None,
                eos_id=eos_id,
            )
            run = jax.jit(
                jax.shard_map(
                    raw, mesh=mesh,
                    in_specs=(self.param_specs(), batch_spec, P()),
                    out_specs=batch_spec, check_vma=False,
                )
            )
            cache[key_] = run
        return run(params, prompt.astype(jnp.int32), jax.random.PRNGKey(seed))

    def _gen_cache_dict(self) -> dict:
        cache = getattr(self, "_gen_cache", None)
        if cache is None:
            cache = self._gen_cache = {}
        return cache

    def _generate_fn(
        self, prompt_len: int, max_new_tokens: int, temperature: float, top_k: int,
        top_p: float = 0.0, tp_axis: str | None = None, jit: bool = True,
        dp_axis: str | None = None, eos_id: int | None = None,
    ):
        """Compiled generate program, cached per (prompt_len, max_new,
        temperature, top_k, top_p) so repeated serving calls don't re-trace.
        ``dp_axis`` (dp-sharded serving) folds each GLOBAL batch row's index
        (this rank's shard offset from that axis) into its sampler key, so a
        dp-sharded run samples per row independently of how the batch is
        split across ranks."""
        key_ = (prompt_len, max_new_tokens, temperature, top_k, top_p, tp_axis, jit,
                dp_axis, eos_id)
        cache = self._gen_cache_dict()
        if key_ in cache:
            return cache[key_]

        def sample(logits, key):
            return sample_token_logits(logits, key, temperature, top_k, top_p)

        def sample_rows(logits, key):
            if dp_axis is None:
                return sample(logits, key)
            b = logits.shape[0]
            row_ids = lax.axis_index(dp_axis) * b + jnp.arange(b)
            keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(row_ids)
            return jax.vmap(lambda lg, kk: sample(lg[None], kk)[0])(logits, keys)

        def run(params, prompt, key):
            logits, kv = self.prefill(params, prompt, tp_axis)
            key, sub = jax.random.split(key)
            first = sample_rows(logits, sub)
            done0 = (
                first == eos_id if eos_id is not None
                else jnp.zeros(first.shape, bool)
            )

            def body(carry, _):
                kv, tok, pos, key, done = carry
                logits, kv = self.decode_step(params, kv, tok, pos, tp_axis)
                key, sub = jax.random.split(key)
                nxt = sample_rows(logits, sub)
                if eos_id is not None:
                    # rows past their EOS keep emitting eos_id (static
                    # shapes — the pad region marks the truncation point)
                    nxt = jnp.where(done, eos_id, nxt)
                    done = done | (nxt == eos_id)
                return (kv, nxt, pos + 1, key, done), nxt

            carry = (kv, first, jnp.asarray(prompt_len, jnp.int32), key, done0)
            _, rest = lax.scan(body, carry, None, length=max_new_tokens - 1)
            return jnp.concatenate([first[None], rest], axis=0).T  # [b, max_new]

        if jit:
            run = jax.jit(run)
        cache[key_] = run
        return run
