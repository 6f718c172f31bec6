"""Hybrid-parallel training: pp × dp × fsdp × sp/cp × tp in one jitted mesh
program.

The composable-mesh-axes design the reference's literature corpus points at
(Megatron PTD-P, OneFlow SBP, Colossal-AI — SURVEY.md §2.3 "hybrid
parallelism: literature only") realized for the transformer:

- params enter TP-sharded (``GPT2.param_specs``), replicated over dp/sp;
  with pp > 1 the layer stack is stage-sharded over 'pp' and runs as a
  GPipe pipeline (``parallel.pp``) inside the same step;
- with fsdp > 1 every param leaf is additionally ZeRO-sharded over the
  'fsdp' axis (``with_fsdp`` specs): inside the per-rank program weights
  are ``all_gather``-ed just-in-time, and the shard_map transpose of that
  gather IS the gradient reduce-scatter — the ZeRO-3 communication
  pattern, spelled as one collective whose autodiff does the rest.
  Optimizer state inherits the sharded layout (ZeRO-1/2 for free);
- the batch enters ``P(('dp','fsdp'), 'sp')`` (batch rows over dp and
  fsdp — fsdp doubles as a data axis, as in ZeRO — sequence over sp);
- inside ``shard_map``, the model runs Megatron TP psums + ring/Ulysses
  sequence-parallel attention; differentiation happens OUTSIDE shard_map so
  every collective's transpose assigns cotangents exactly once;
- the optimizer update runs OUTSIDE shard_map in the same jit — GSPMD
  propagates the param shardings through optax states automatically.

One step = one XLA program; every collective rides ICI.
"""

from __future__ import annotations

from typing import Callable

import jax
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dsml_tpu.parallel.mesh import MeshSpec

__all__ = ["shard_params", "make_hybrid_train_step", "hybrid_loss_fn",
           "default_attn_impl"]


def default_attn_impl(mesh: Mesh) -> str:
    """What ``attn_impl=None`` resolves to on this mesh: the context-parallel
    flash ring (``"ring2"``: bidirectional KV streaming, causal hop skip, KV
    re-streaming backward — ``ops.ring_attention``) when cp is sized, else
    the exact XLA ring. ONE definition, shared by the train-step builder and
    any caller (e.g. the example's eval loss) that must match it."""
    return "ring2" if mesh.shape.get("cp", 1) > 1 else "ring"


def shard_params(params, mesh: Mesh, specs) -> dict:
    """Place a param pytree onto the mesh per its PartitionSpec pytree."""
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def gather_fsdp(params, pspecs, axis: str = "fsdp"):
    """Reconstruct full weights from their ZeRO shards inside the per-rank
    program: one tiled ``all_gather`` over ``axis`` per fsdp-sharded leaf.
    Under ``jax.grad`` of the surrounding shard_map, the transpose of each
    gather is a ``psum_scatter`` — gradients leave reduce-scattered into the
    same shard layout, which is exactly ZeRO's backward half."""

    def g(leaf, spec):
        for dim, ax in enumerate(spec):
            if ax == axis:
                return lax.all_gather(leaf, axis, axis=dim, tiled=True)
        return leaf

    return jax.tree.map(g, params, pspecs, is_leaf=lambda x: isinstance(x, P))


def hybrid_loss_fn(
    model, attn_impl: str = "ring", pp_axis: str | None = None, n_micro: int = 1,
    seq_axis: str = "sp",
) -> Callable:
    """Per-rank loss closure for shard_map over the framework mesh axes.

    ``seq_axis`` names the mesh axis the sequence dimension shards over —
    the legacy ``"sp"`` ring or the ``"cp"`` context-parallel axis; the
    model's per-rank positions offset by the shard origin on whichever is
    passed, and the per-rank loss (chunked xent — ``ops/xent.py``) runs on
    this rank's sequence rows alone, so the [B, S, vocab] logits tensor is
    never assembled on any chip."""

    def loss_fn(params, x, y):
        return model.loss_spmd(
            params, x, y, tp_axis="tp", sp_axis=seq_axis, attn_impl=attn_impl,
            pp_axis=pp_axis, n_micro=n_micro,
        )

    return loss_fn


def _with_step_watermark(jitted):
    """Wrap a jitted hybrid step so every call lands a memory-ledger peak
    watermark (docs/OBSERVABILITY.md § Memory ledger) — one enabled
    check when obs is off, one cached stats-availability check on
    backends without ``memory_stats``. ``.lower`` passes through so
    compile-introspection callers (memory analysis) keep working."""
    from dsml_tpu.obs.memory import get_memory_ledger

    ledger = get_memory_ledger()

    def step(params, opt_state, x, y):
        out = jitted(params, opt_state, x, y)
        ledger.note_step_peak()
        return out

    step.lower = jitted.lower
    step.jitted = jitted
    return step


def make_hybrid_train_step(
    model,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    attn_impl: str | None = None,
    grad_accum: int = 1,
    n_microbatches: int = 1,
    schedule: str = "gpipe",
    dp_sync: str = "xla",
    bucket_size_mb: float | None | str = "auto",
):
    """Build ``step(params, opt_state, x, y) -> (params, opt_state, loss)``.

    ``x``/``y``: int32 [global_batch, seq]; with ``grad_accum > 1`` the
    global batch is split into that many microbatches whose gradients
    accumulate on-device before one optimizer update (BASELINE.md's
    "data-parallel AllReduce + grad accumulation" config).

    With mesh cp > 1 (context parallelism) the SEQUENCE dimension shards
    over the ``cp`` ring: attention streams KV blocks around the axis
    (``attn_impl=None`` resolves to ``"ring2"`` — the bidirectional flash
    ring with causal hop skipping and the KV re-streaming backward,
    ``ops.ring_attention``), per-rank positions offset by the shard origin,
    and the loss stays sequence-parallel (each rank's chunked xent over its
    own rows + one pmean) so neither full-length activations nor the
    [B, S, vocab] logits ever exist on one chip. cp composes with dp/fsdp
    (and pp/tp) like sp does; sp and cp cannot both exceed 1 — a 2D
    sequence grid rides tp × sp via ``ops.attention.attention_2d`` instead.
    Selective remat (``config.remat="mlp"``) composes: the flash residuals
    each cp rank keeps are O(S/cp).

    When the mesh has pp > 1, the transformer block stack additionally runs
    as a pipeline of ``n_microbatches`` per step (params must be the
    STACKED form from :func:`init_hybrid`): the full pp×dp×sp×tp hybrid.
    ``schedule`` picks the pipeline schedule:

    - ``"gpipe"`` — synchronous GPipe: forward scan + ``jax.grad``'s
      mirrored backward; stores one residual set per tick (O(M) activation
      memory) unless ``config.remat`` rematerializes stages.
    - ``"1f1b"`` — hand-interleaved one-forward-one-backward
      (``parallel.pp.pipeline_train_1f1b``): each microbatch's backward
      starts as soon as its forward completes, in-flight activations are
      schedule-bounded at ≤ 2(pp−1)+1 microbatches with stage recompute.
      Same bubble fraction as GPipe (synchronous flush), much flatter
      memory in M.

    ``dp_sync`` picks the gradient-sync mechanism on dp-ONLY meshes (every
    other axis size 1): ``"xla"`` (default) keeps the shard_map-transpose
    psum — one sync per microbatch, XLA's collective choice. Any explicit
    algorithm (``"ring"``/``"ring2"``/``"naive"``/``"auto"``/``"q8"``, or
    the block-quantized ring family ``"q8_ring"``/``"q8_ring2"``/
    ``"q4_ring"``/``"q4_ring2"``/``"quant"`` — int8/int4 inside the
    2(n−1)-step schedule, ``DSML_QUANT`` resolves ``"quant"`` per dtype)
    instead accumulates LOCAL per-rank gradients across the grad-accum
    microbatches and syncs ONCE per step as per-bucket collectives
    (``parallel.bucketing``, ~``bucket_size_mb`` MiB each, ``"auto"`` =
    the 4 MiB env default, ``None`` = one buffer) — grad_accum× fewer
    bytes on the wire and per-bucket overlap with the backward. Grad-accum
    composes especially well with the quantized syncs: accumulation stays
    full-precision on-device, so quantization noise enters once per step,
    not once per microbatch. (Error-feedback residual state threads
    through the dp/zero2 step builders, which own their state signatures —
    here use ``parallel.dp.make_dp_train_step(error_feedback=True)`` for
    the EF variant.) Per-rank differentiation is exact here precisely
    because no collective crosses ranks inside the loss on a dp-only mesh;
    meshes with tp/sp/pp/fsdp > 1 reject explicit ``dp_sync`` rather than
    compute silently-wrong cotangents.
    """
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    pp_size = mesh.shape.get("pp", 1)
    pp_axis = "pp" if pp_size > 1 else None
    fsdp_size = mesh.shape.get("fsdp", 1)
    # ONE definition of the sequence-axis policy (MeshSpec.seq_axis: cp wins
    # when sized, sp>1 with cp>1 rejected); the batch spec names both axes
    # so either composes with dp/fsdp
    seq_axis = MeshSpec.from_mesh(mesh).seq_axis()
    seq_names = tuple(a for a in ("sp", "cp") if a in mesh.axis_names)
    if attn_impl is None:
        attn_impl = default_attn_impl(mesh)
    if schedule == "1f1b" and not pp_axis:
        # silent fallback would let a user "measure 1F1B" on a pipeline-less
        # mesh and actually measure the gpipe path
        raise ValueError("schedule='1f1b' requires a mesh with pp > 1")
    if schedule == "1f1b" and getattr(model.config, "pp_interleave", 1) > 1:
        raise ValueError("pp_interleave > 1 composes with the gpipe schedule only")
    pspecs = model.param_specs(pp=bool(pp_axis), fsdp=fsdp_size)
    # fsdp doubles as a data axis (ZeRO): batch rows shard over dp × fsdp;
    # the sequence dim shards over whichever sequence ring is sized
    batch_spec = P(("dp", "fsdp"), seq_names)
    loss_fn = hybrid_loss_fn(model, attn_impl, pp_axis, n_microbatches, seq_axis)
    # value= lets loss-reactive transforms (utils.schedules.adaptive_plateau)
    # see the loss; the wrapper makes every optimizer accept it
    optimizer = optax.with_extra_args_support(optimizer)

    def total_loss(params, x, y):
        # JIT weight reconstruction from ZeRO shards; the transpose of the
        # gathers reduce-scatters the gradients back into shard layout
        params = gather_fsdp(params, pspecs)
        # pmean over the batch axes so the per-rank value is the GLOBAL mean
        # loss, replicated on every rank (tp ranks agree by construction of
        # the vocab-sharded CE; pp ranks via the masked-head psum). cp/sp
        # ranks hold equal-length sequence shards, so the mean of per-rank
        # means IS the global mean — the sequence-parallel loss.
        return lax.pmean(loss_fn(params, x, y), ("dp", "fsdp") + seq_names)

    sharded_loss = jax.shard_map(
        total_loss,
        mesh=mesh,
        in_specs=(pspecs, batch_spec, batch_spec),
        out_specs=P(),
        check_vma=False,
    )

    def gpipe_grads(params, x, y):
        # Differentiate OUTSIDE shard_map: the outer grad seeds the
        # replicated loss once and shard_map's transpose machinery assigns
        # every collective's cotangent correctly (psum of per-rank
        # contributions for replicated params, per-stage cotangents for
        # pp-sharded layers). value_and_grad INSIDE shard_map would seed 1
        # per rank and inflate every psum-crossing gradient by the axis size
        # (tp, and pp's masked-head psum) — a silent n× lr scale.
        return jax.value_and_grad(sharded_loss)(params, x, y)

    def _1f1b_per_rank(params, x, y):
        # 1F1B differentiates INSIDE shard_map (per-tick jax.vjp — that is
        # what lets forward and backward interleave), which is sound only
        # under check_vma=True: vma tracking gives collective transposes
        # their exact cotangents, and the transpose of each auto-lifted
        # replicated input psums its cotangent across the lifted axes right
        # inside the per-tick vjp. With the schedule's seed carrying the
        # 1/(M·n_dp·n_fsdp·n_sp) normalization, grads therefore arrive
        # already reduced to each leaf's replication — no further psums.
        #
        # fsdp composes through an EXPLICIT vjp of the weight gather: the
        # schedule sees full weights (marked fsdp-varying, so its per-tick
        # transposes leave their cotangents per-rank), and pulling the
        # accumulated full-weight grads back through the gather's transpose
        # is one psum_scatter per sharded leaf — summing the fsdp data
        # ranks AND scattering into shard layout, exactly ZeRO's backward
        # half (the same collective the gpipe path gets from shard_map's
        # outer-grad transpose). Leaves without an fsdp dim pass through
        # untouched and their fsdp reduction happens via the schedule's
        # auto-lift psums like any replicated param.
        full, fsdp_vjp = jax.vjp(lambda p: gather_fsdp(p, pspecs), params)
        loss, grads_full = model.train_grads_1f1b_spmd(
            full, x, y, tp_axis="tp", sp_axis=seq_axis, attn_impl=attn_impl,
            pp_axis="pp", n_micro=n_microbatches,
            # the batch enters P(('dp','fsdp'), seq axes): data varies over
            # fsdp too (size 1 on fsdp-less meshes, but vma tracking still
            # sees it)
            batch_axes=("dp", "fsdp") + seq_names,
        )
        # loss is masked to the last pp rank; batch axes hold genuinely
        # different values (mean them); remaining marked axes (tp) hold
        # equal values (pmean is an identity that clears the marking)
        loss = lax.psum(loss, "pp")
        rest = tuple(jax.typeof(loss).vma)
        if rest:
            loss = lax.pmean(loss, rest)
        (grads,) = fsdp_vjp(grads_full)
        return loss, grads

    if dp_sync != "xla":
        # per-rank value_and_grad + one explicit bucketed sync is only
        # exact when NO collective crosses ranks inside the loss — i.e. a
        # dp-only mesh (psums over the size-1 tp/sp/pp axes are identities)
        busy = {a: s for a in ("pp", "fsdp", "sp", "cp", "tp")
                if (s := mesh.shape.get(a, 1)) > 1}
        if busy:
            raise ValueError(
                f"dp_sync={dp_sync!r} requires a dp-only mesh; got {busy} — "
                "use dp_sync='xla' on multi-axis meshes"
            )
        from dsml_tpu.ops.collectives import ReduceOp
        from dsml_tpu.parallel.bucketing import bucketed_all_reduce, default_bucket_mb

        mb = default_bucket_mb() if bucket_size_mb == "auto" else bucket_size_mb

        def _explicit_per_rank(params, x, y):
            def micro_grads(p, xm, ym):
                return jax.value_and_grad(loss_fn)(p, xm, ym)

            if grad_accum == 1:
                loss, grads = micro_grads(params, x, y)
            else:
                micro = x.shape[0] // grad_accum
                xs = x[: micro * grad_accum].reshape(grad_accum, micro, *x.shape[1:])
                ys = y[: micro * grad_accum].reshape(grad_accum, micro, *y.shape[1:])

                def body(carry, xy):
                    loss_acc, grads_acc = carry
                    loss, grads = micro_grads(params, *xy)
                    return (loss_acc + loss,
                            jax.tree.map(jax.numpy.add, grads_acc, grads)), None

                zero = jax.tree.map(jax.numpy.zeros_like, params)
                (loss, grads), _ = jax.lax.scan(body, (0.0, zero), (xs, ys))
                loss = loss / grad_accum
                grads = jax.tree.map(lambda g: g / grad_accum, grads)
            # the step's ONLY cross-rank exchange: per-bucket collectives,
            # once per step regardless of grad_accum
            from dsml_tpu.obs import record_collective_plan

            # trace-time: bucket plan labeled by algorithm, once per compile
            record_collective_plan(dp_sync, grads, mb, "dp")
            grads = bucketed_all_reduce(grads, "dp", ReduceOp.AVG, dp_sync, mb)
            return lax.pmean(loss, "dp"), grads

        explicit_step_grads = jax.shard_map(
            _explicit_per_rank,
            mesh=mesh,
            in_specs=(pspecs, batch_spec, batch_spec),
            out_specs=(P(), pspecs),
            check_vma=False,
        )

        n_dp = mesh.shape.get("dp", 1)

        def step(params, opt_state, x, y):
            # the microbatch split runs on each rank's SHARD inside
            # shard_map, so per-rank rows must divide — global-only
            # divisibility would silently drop rows (or give 0-row
            # microbatches) whenever batch/dp % grad_accum != 0
            if grad_accum > 1 and x.shape[0] % (grad_accum * n_dp):
                raise ValueError(
                    f"global batch {x.shape[0]} not divisible by "
                    f"grad_accum*dp = {grad_accum}*{n_dp}"
                )
            loss, grads = explicit_step_grads(params, x, y)
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(grads, opt_state, params, value=loss)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return _with_step_watermark(jax.jit(step, donate_argnums=(0, 1)))

    if pp_axis and schedule == "1f1b":
        sharded_grads = jax.shard_map(
            _1f1b_per_rank,
            mesh=mesh,
            in_specs=(pspecs, batch_spec, batch_spec),
            out_specs=(P(), pspecs),
            check_vma=True,
        )
    else:
        sharded_grads = gpipe_grads

    def step(params, opt_state, x, y):
        if grad_accum == 1:
            loss, grads = sharded_grads(params, x, y)
        else:
            if x.shape[0] % grad_accum:
                raise ValueError(
                    f"global batch {x.shape[0]} not divisible by grad_accum={grad_accum}"
                )
            micro = x.shape[0] // grad_accum
            xs = x[: micro * grad_accum].reshape(grad_accum, micro, *x.shape[1:])
            ys = y[: micro * grad_accum].reshape(grad_accum, micro, *y.shape[1:])

            def body(carry, xy):
                loss_acc, grads_acc = carry
                loss, grads = sharded_grads(params, *xy)
                return (loss_acc + loss, jax.tree.map(jax.numpy.add, grads_acc, grads)), None

            zero = jax.tree.map(jax.numpy.zeros_like, params)
            (loss, grads), _ = jax.lax.scan(body, (0.0, zero), (xs, ys))
            loss = loss / grad_accum
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params, value=loss)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return _with_step_watermark(jax.jit(step, donate_argnums=(0, 1)))


def init_hybrid(model, optimizer, mesh: Mesh, seed: int = 0):
    """Initialize (params, opt_state) already placed on the mesh. With
    pp > 1 the layer list is stacked (leading layer axis) and stage-sharded
    over 'pp'; with fsdp > 1 leaves are ZeRO-sharded over 'fsdp'."""
    params = model.init(seed)
    pp = mesh.shape.get("pp", 1) > 1
    fsdp_size = mesh.shape.get("fsdp", 1)
    if pp:
        from dsml_tpu.parallel.pp import interleave_layer_order, stack_layer_params

        n_layer = len(params["layers"])
        pp_size = mesh.shape["pp"]
        if n_layer % pp_size:
            raise ValueError(f"n_layer={n_layer} not divisible by pp={pp_size}")
        v = getattr(model.config, "pp_interleave", 1)
        layers = params["layers"]
        if v > 1:
            # interleaved schedule: rank r owns chunks r, r+S, … — permute
            # the layer order so the plain P('pp') shard hands each rank
            # exactly its v chunks (pp.interleave_layer_order)
            order = interleave_layer_order(n_layer, pp_size, v)
            layers = [layers[i] for i in order]
        params = {**params, "layers": stack_layer_params(layers)}
    params = shard_params(params, mesh, model.param_specs(pp=pp, fsdp=fsdp_size))
    opt_state = jax.jit(optimizer.init)(params)

    # leaves jit creates from scratch (adam's step count) come back on a
    # single device with no mesh sharding; the live run tolerates the mix,
    # but a checkpoint RESTORE of such a leaf comes back committed and then
    # collides with the mesh-placed params inside the jitted step — pin
    # every leaf to the mesh now so saved templates carry real shardings
    def pin(leaf):
        if isinstance(leaf, jax.Array) and not isinstance(leaf.sharding, NamedSharding):
            return jax.device_put(leaf, NamedSharding(mesh, P()))
        return leaf

    opt_state = jax.tree.map(pin, opt_state)
    # ledger attribution at the allocation site: per-device SHARD bytes
    # (an fsdp/pp-sharded state claims what one chip actually holds) —
    # no-op when obs is off
    from dsml_tpu.obs.memory import get_memory_ledger

    ledger = get_memory_ledger()
    ledger.claim_tree("params", params, detail="hybrid")
    ledger.claim_tree("optimizer", opt_state, detail="hybrid")
    return params, opt_state
