"""Int8 stochastic quantization + compressed gradient sync, the
block-quantized ring schedules (int8/int4 inside the 2(n−1)-step ring,
EQuARX-style — ISSUE 9), and the hierarchical two-level all-reduce
(communication/memory literature parity, SURVEY.md §2.4 folders 6-7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dsml_tpu.ops.collectives import (
    ReduceOp,
    all_reduce,
    hierarchical_all_reduce,
    ring_wire_bytes,
)
from dsml_tpu.ops.quantization import (
    QuantizedTensor,
    compressed_all_reduce,
    compressed_checkpoint,
    dequantize_int8,
    default_qblock,
    get_scheme,
    pack_int4,
    quant_algorithm_for,
    quantize_int8,
    quantize_roundtrip,
    quantized_flat_reduce_scatter,
    quantized_ring_all_reduce,
    quantized_ring_wire_bytes,
    unpack_int4,
)


def test_weight_only_int8_small_default():
    """Default-suite representative of w8a16 serving: GPT-2 prefill logits
    stay close under per-channel int8 weights, and the plain batcher serves
    the quantized params token-exactly (the two-family × speculative matrix
    runs under -m slow)."""
    from dsml_tpu.models.common import quantize_weights_int8
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.serving import ContinuousBatcher

    model = GPT2(GPT2Config.tiny())
    params = model.init(23)
    qp = quantize_weights_int8(params)
    rng = np.random.default_rng(23)
    prompt = jnp.asarray(rng.integers(0, 512, (2, 12)), jnp.int32)
    lf, _ = model.prefill(params, prompt, last_index=11)
    lq, _ = model.prefill(qp, prompt, last_index=11)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lf), atol=0.05, rtol=0)

    ref = np.asarray(model.generate(qp, prompt[:1], 6))[0].tolist()
    srv = ContinuousBatcher(model, qp, n_slots=2, prompt_buckets=(16,))
    rid = srv.submit(np.asarray(prompt[0]), 6)
    assert srv.run()[rid] == ref


@pytest.mark.slow
def test_weight_only_int8_serving_close_and_scheduling_exact():
    """Weight-only int8 (w8a16): quantized params serve every single-device
    decode surface with logits close to full precision, and the batcher's
    scheduling-independence stays EXACT under quantization (the quantized
    model is just another model)."""
    from dsml_tpu.models.common import quantize_weights_int8
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.models.llama import Llama, LlamaConfig
    from dsml_tpu.serving import ContinuousBatcher

    for model in (GPT2(GPT2Config.tiny()), Llama(LlamaConfig.tiny())):
        name = type(model).__name__
        params = model.init(23)
        qp = quantize_weights_int8(params)
        rng = np.random.default_rng(23)
        prompt = jnp.asarray(rng.integers(0, 512, (2, 12)), jnp.int32)
        lf, _ = model.prefill(params, prompt, last_index=11)
        lq, _ = model.prefill(qp, prompt, last_index=11)
        # per-channel absmax int8 on ~N(0, 0.02) weights: tiny logit drift
        np.testing.assert_allclose(np.asarray(lq), np.asarray(lf), atol=0.05,
                                   rtol=0, err_msg=name)

        # the batcher (incl. speculative) serves the quantized params and
        # matches the quantized generate token-for-token
        ref = np.asarray(model.generate(qp, prompt[:1], 6))[0].tolist()
        for kw in ({}, {"speculative_window": 4}):
            srv = ContinuousBatcher(model, qp, n_slots=2, prompt_buckets=(16,), **kw)
            rid = srv.submit(np.asarray(prompt[0]), 6)
            out = srv.run()
            assert out[rid] == ref, (name, kw)


def test_weight_only_int8_shrinks_block_weights():
    """The quantized pytree's block matmul weights are int8 (≈4x below
    f32 + a thin scale row); embeddings/norms/biases stay full width."""
    from dsml_tpu.models.common import quantize_weights_int8
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config

    model = GPT2(GPT2Config.tiny())
    params = model.init(0)
    qp = quantize_weights_int8(params)

    def nbytes(t):
        return sum(v.size * v.dtype.itemsize for v in jax.tree.leaves(t))

    for group in ("attn", "mlp"):
        full = nbytes(params["layers"][0][group])
        quant = nbytes(qp["layers"][0][group])
        assert quant < full / 2.5, (group, quant, full)
    assert qp["layers"][0]["attn"]["wqkv"]["qw"].dtype == jnp.int8
    assert qp["wte"].dtype == params["wte"].dtype  # embeddings untouched


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1000,)) * 3.0, jnp.float32)
    qt = quantize_int8(x, seed=1)
    back = dequantize_int8(qt)
    assert back.shape == x.shape and back.dtype == x.dtype
    # per-block absmax scaling bounds the element error by one quantum
    scale_per_elem = np.repeat(np.asarray(qt.scales)[:, 0], qt.values.shape[1])[:1000]
    assert np.all(np.abs(np.asarray(back - x)) <= scale_per_elem + 1e-6)


def test_quantize_stochastic_rounding_unbiased():
    """Averaging many independently-seeded round-trips must converge to x —
    the property that keeps compressed gradients from biasing SGD."""
    x = jnp.full((512,), 0.303, jnp.float32)  # deliberately between quanta
    reps = 200
    acc = np.zeros(512, np.float64)
    for s in range(reps):
        acc += np.asarray(dequantize_int8(quantize_int8(x, seed=s)), np.float64)
    mean_err = np.abs(acc / reps - 0.303).max()
    scale = float(quantize_int8(x, seed=0).scales.max())
    assert mean_err < 0.2 * scale, (mean_err, scale)  # deterministic rounding would sit at ~0.5 quanta


def test_quantized_values_in_range():
    x = jnp.asarray(np.random.default_rng(1).standard_normal(2048) * 100, jnp.float32)
    qt = quantize_int8(x, seed=2)
    v = np.asarray(qt.values)
    assert v.dtype == np.int8 and v.min() >= -127 and v.max() <= 127


def test_compressed_all_reduce_close_to_exact(mesh8):
    rng = np.random.default_rng(3)
    x = rng.standard_normal((8, 4096)).astype(np.float32)
    exact = x.mean(axis=0)

    got = jax.jit(
        jax.shard_map(
            lambda s: compressed_all_reduce(s[0], "dev", seed=7)[None],
            mesh=mesh8, in_specs=P("dev"), out_specs=P("dev"), check_vma=False,
        )
    )(jnp.asarray(x))
    got0 = np.asarray(got)[0]
    # every rank's copy equals the same compressed mean
    scale_bound = np.abs(x).max() / 127.0
    assert np.abs(got0 - exact).max() < scale_bound, (np.abs(got0 - exact).max(), scale_bound)


def test_q8_training_converges(dp_mesh8):
    from dsml_tpu.models.mlp import MLP
    from dsml_tpu.trainer import TrainConfig, Trainer
    from dsml_tpu.utils.data import synthetic_classification

    data = synthetic_classification(512, 64, classes=4, seed=0)
    cfg = TrainConfig(epochs=3, batch_size=64, lr=0.05, optimizer="momentum", algorithm="q8")
    trainer = Trainer(MLP(sizes=(64, 32, 4)), cfg, mesh=dp_mesh8)
    _, history, test_acc = trainer.train(data)
    assert history[-1]["avg_loss"] < history[0]["avg_loss"]
    assert test_acc > 0.8


def _two_layer(params, x):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return h @ params["w2"]


def _tiny_params(rng):
    return {
        "w1": jnp.asarray(rng.standard_normal((32, 64)) * 0.1, jnp.float32),
        "b1": jnp.zeros((64,), jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((64, 32)) * 0.1, jnp.float32),
    }


def test_compressed_checkpoint_forward_exact():
    """The forward pass is untouched — compression affects only the stash."""
    rng = np.random.default_rng(5)
    params = _tiny_params(rng)
    x = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(compressed_checkpoint(_two_layer)(params, x)),
        np.asarray(_two_layer(params, x)),
    )


def test_compressed_checkpoint_grads_close_and_int8_stash():
    rng = np.random.default_rng(6)
    params = _tiny_params(rng)
    x = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)

    def loss(f):
        return lambda p, xx: jnp.sum(f(p, xx) ** 2)

    g_exact = jax.grad(loss(_two_layer), argnums=(0, 1))(params, x)
    wrapped = compressed_checkpoint(_two_layer, seed=3)
    g_comp = jax.jit(jax.grad(loss(wrapped), argnums=(0, 1)))(params, x)
    # gradient error is bounded by the input quantization noise, which is
    # ~|x|_blockmax/127 per element — small relative to the grads themselves
    for e, c in zip(jax.tree.leaves(g_exact), jax.tree.leaves(g_comp)):
        denom = np.abs(np.asarray(e)).max() + 1e-6
        assert np.abs(np.asarray(e - c)).max() / denom < 0.05

    # the residual that crosses the vjp boundary really is the int8 stash
    _, vjp_fn = jax.vjp(lambda p, xx: wrapped(p, xx), params, x)
    stash_dtypes = {
        str(l.dtype) for l in jax.tree.leaves(vjp_fn) if hasattr(l, "dtype")
    }
    assert "int8" in stash_dtypes, stash_dtypes


def test_compressed_checkpoint_int_leaves_pass_through():
    """Integer activations (token ids) must be stashed exactly, not quantized."""
    emb = jnp.asarray(np.random.default_rng(7).standard_normal((16, 8)), jnp.float32)

    def fn(params, x):
        return params[x["ids"]] * x["scale"]

    ids = jnp.arange(4, dtype=jnp.int32)
    x = {"ids": ids, "scale": jnp.ones((4, 1), jnp.float32)}
    g = jax.grad(lambda p: jnp.sum(compressed_checkpoint(fn)(p, x)))(emb)
    g_ref = jax.grad(lambda p: jnp.sum(fn(p, x)))(emb)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-6)


def test_compressed_checkpoint_under_shard_map_with_collective(mesh8):
    """fn containing a psum (the TP pattern): the backward's vjp must
    transpose the collective correctly from inside the custom_vjp."""
    rng = np.random.default_rng(8)
    w = jnp.asarray(rng.standard_normal((8, 16, 4)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((8, 2, 16)), jnp.float32)

    def fn(params, xx):  # row-parallel matmul: psum of partial products
        return jax.lax.psum(xx @ params, "dev")

    def per_rank(make):
        def run(w_shard, x_shard):
            y = make(fn)(w_shard[0], x_shard[0])
            return jnp.sum(y * y)[None]

        return jax.shard_map(
            run, mesh=mesh8, in_specs=(P("dev"), P("dev")), out_specs=P("dev"),
            check_vma=False,
        )

    def total(make):
        return lambda ww: jnp.sum(per_rank(make)(ww, x)) / 8

    g_ref = jax.grad(total(lambda f: f))(w)
    g_comp = jax.jit(jax.grad(total(compressed_checkpoint)))(w)
    denom = np.abs(np.asarray(g_ref)).max()
    assert np.abs(np.asarray(g_ref - g_comp)).max() / denom < 0.05


def test_quantized_tensor_static_metadata():
    """size/shape/dtype are aux_data, not traced leaves — the property that
    lets QuantizedTensor cross jit boundaries as a residual."""
    qt = quantize_int8(jnp.ones((10,), jnp.float32))
    leaves, treedef = jax.tree.flatten(qt)
    assert len(leaves) == 2  # values, scales only
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert isinstance(rebuilt, QuantizedTensor) and rebuilt.size == 10


# ---------------------------------------------------------------------------
# Block-quantized ring schedules (ISSUE 9)
# ---------------------------------------------------------------------------


def _quant_ring(mesh8, x, scheme, bidirectional, **kw):
    return jax.jit(jax.shard_map(
        lambda s: quantized_ring_all_reduce(
            s[0], "dev", scheme, bidirectional=bidirectional, **kw
        )[None],
        mesh=mesh8, in_specs=P("dev"), out_specs=P("dev"), check_vma=False,
    ))(jnp.asarray(x))


@pytest.mark.parametrize("scheme,bidirectional", [
    ("int8", False), ("int8", True), ("int4", False), ("int4", True),
])
@pytest.mark.parametrize("size", [4096, 1000, 17])
def test_quantized_ring_close_and_identical_across_ranks(
    mesh8, scheme, bidirectional, size
):
    """The quantized ring's mean stays within the scheme's quantization
    noise of the exact mean, and — because the all-gather half circulates
    each owner's wire bytes unchanged — every rank's copy is BIT-IDENTICAL
    (the all-reduce postcondition, which per-hop requantization on the
    gather path would break). Sizes straddle block (512) and segment
    boundaries: 1000 and 17 exercise the zero-padded tails."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((8, size)).astype(np.float32)
    got = np.asarray(_quant_ring(mesh8, x, scheme, bidirectional))
    for r in range(1, 8):
        np.testing.assert_array_equal(got[r], got[0], err_msg=f"rank {r}")
    exact = x.mean(axis=0)
    qmax = get_scheme(scheme).qmax
    # per-hop error ≤ one quantum of the partial sums (absmax ≤ n·|x|max);
    # n−1 accumulating hops + the final gather quantization, ÷n for AVG
    bound = 8 * np.abs(x).max() / qmax
    assert np.abs(got[0] - exact).max() < bound, (
        np.abs(got[0] - exact).max(), bound
    )


def test_quantized_ring_pad_never_leaks(mesh8):
    """Non-multiple-of-block tails: the ring zero-pads up to a multiple of
    directions·n·block, and those pad lanes must NEVER leak into the
    dequantized output (ISSUE 9 satellite). An all-ones payload makes any
    leak visible: a pad lane bleeding into a real lane would pull it off
    1.0 by a whole quantum, far above the scheme's rounding noise on a
    constant block (which quantizes EXACTLY: absmax scaling maps the
    constant to ±qmax)."""
    for size in (1, 511, 513, 4095, 4097):
        x = np.ones((8, size), np.float32)
        for scheme in ("int8", "int4"):
            got = np.asarray(_quant_ring(mesh8, x, scheme, False))[0]
            # constant blocks round-trip exactly — any deviation is a leak
            np.testing.assert_allclose(
                got, np.ones(size, np.float32), rtol=0, atol=1e-6,
                err_msg=f"scheme={scheme} size={size}",
            )
    # and the v1 quantize_int8 pad (inside _blocked) stays internal too
    odd = jnp.asarray(np.ones(777, np.float32))
    np.testing.assert_allclose(
        np.asarray(dequantize_int8(quantize_int8(odd, seed=5))),
        np.ones(777, np.float32), rtol=0, atol=1e-6,
    )


def test_quantized_ring_sum_and_deterministic(mesh8):
    rng = np.random.default_rng(4)
    x = rng.standard_normal((8, 600)).astype(np.float32)
    got = np.asarray(
        _quant_ring(mesh8, x, "int8", False, mean=False, stochastic=False)
    )[0]
    bound = 8 * 8 * np.abs(x).max() / 127
    assert np.abs(got - x.sum(axis=0)).max() < bound
    # deterministic rounding: same input, same bits — the property that
    # makes an EF run's kill-and-resume bit-identical
    again = np.asarray(
        _quant_ring(mesh8, x, "int8", False, mean=False, stochastic=False)
    )[0]
    np.testing.assert_array_equal(got, again)


def test_quantized_ring_rejects_integer_payloads(mesh8):
    with pytest.raises(ValueError, match="float"):
        jax.jit(jax.shard_map(
            lambda s: quantized_ring_all_reduce(s[0], "dev")[None],
            mesh=mesh8, in_specs=P("dev"), out_specs=P("dev"), check_vma=False,
        ))(jnp.zeros((8, 64), jnp.int32))


@pytest.mark.parametrize("size", [4096, 4099, 63])
def test_quantized_reduce_scatter_layout_matches_flat(mesh8, size):
    """Rank i is left with contiguous segment i (flat_reduce_scatter's
    contract) and the values track the fp32 reduce-scatter within
    quantization noise — the shard length matches the unquantized path's
    exactly, so ZeRO-2's sharded optimizer state fits unchanged."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((8, size)).astype(np.float32)

    def rs(s):
        shard, padded = quantized_flat_reduce_scatter(s[0], "dev", "int8")
        assert padded == -(-size // 8) * 8  # static: the n-multiple rule
        return shard[None]

    got = np.asarray(jax.jit(jax.shard_map(
        rs, mesh=mesh8, in_specs=P("dev"), out_specs=P("dev"), check_vma=False,
    ))(jnp.asarray(x))).reshape(-1)
    padded = -(-size // 8) * 8
    exact = np.zeros(padded, np.float32)
    exact[:size] = x.mean(axis=0)
    bound = 8 * np.abs(x).max() / 127
    assert np.abs(got - exact).max() < bound


def test_error_feedback_recovers_sub_quantum_gradients(mesh8):
    """THE error-feedback property: a persistent gradient component too
    small for the DETERMINISTIC quantizer (round-to-nearest floors it to
    zero every hop) is lost forever on its own, but with the residual
    folded back in it accumulates until it crosses a quantum and the
    delivered mass catches up (EF-SGD's claim, here pinned on the real
    ring). The no-EF production path dithers stochastically instead —
    unbiased in expectation — so the honest contrast is against the same
    deterministic compressor EF actually corrects."""
    from dsml_tpu.parallel.bucketing import bucketed_all_reduce

    block = default_qblock()
    # one large element pins the block scale; the rest sit far below half
    # a quantum, so round-to-nearest drops them every single step
    base = np.zeros((8, block), np.float32)
    base[:, 0] = 1.0
    small = 0.003  # quantum = 1/127 ≈ 0.00787
    base[:, 1:] = small

    def sync(stacked, ef_stacked, use_ef):
        def fn(s, e):
            tree = {"g": s[0]}
            if use_ef:
                out, new_ef = bucketed_all_reduce(
                    tree, "dev", ReduceOp.AVG, "q8_ring", 4.0,
                    error_feedback={"g": e[0]},
                )
                return out["g"][None], new_ef["g"][None]
            out = quantized_ring_all_reduce(s[0], "dev", "int8", stochastic=False)
            return out[None], e

        return jax.jit(jax.shard_map(
            fn, mesh=mesh8, in_specs=(P("dev"), P("dev")),
            out_specs=(P("dev"), P("dev")), check_vma=False,
        ))(stacked, ef_stacked)

    steps = 10
    for use_ef in (False, True):
        ef = jnp.zeros((8, block), jnp.float32)
        delivered = np.zeros(block, np.float64)
        for _ in range(steps):
            out, ef = sync(jnp.asarray(base), ef, use_ef)
            delivered += np.asarray(out)[0]
        want = steps * small
        got_small = delivered[1:].mean()
        if use_ef:
            # delivered mass within one quantum of the true total
            assert abs(got_small - want) < 1.5 / 127, (got_small, want)
        else:
            # deterministic rounding without EF: sub-quantum mass vanishes
            assert got_small < want / 10, (got_small, want)


def test_wire_bytes_reduction_at_least_2x():
    """The acceptance bar's counting argument: at equal payload the
    quantized ring ships ≥2× fewer bytes than the fp32 ring (int8 ≈4×,
    int4 ≈8× — bits/8 + 4/block per element vs 4)."""
    n_elems = 1 << 20
    fp32 = ring_wire_bytes(n_elems, 8)
    for scheme, floor in (("int8", 3.5), ("int4", 7.0)):
        for bidir in (False, True):
            q = quantized_ring_wire_bytes(n_elems, 8, scheme, bidir)
            assert fp32 / q >= floor >= 2.0, (scheme, bidir, fp32 / q)
    assert ring_wire_bytes(n_elems, 1) == 0
    assert quantized_ring_wire_bytes(n_elems, 1) == 0


def test_pack_int4_bit_identical_to_gpt2_kv_cache():
    """The shared nibble helpers reproduce the ORIGINAL GPT-2 KV-cache
    packing bit-for-bit (ISSUE 9 satellite: one helper, two callers). The
    reference implementation here is the pre-unification inline code,
    copied verbatim."""
    rng = np.random.default_rng(7)
    x32 = jnp.asarray(rng.standard_normal((2, 3, 5, 16)) * 2.0, jnp.float32)
    a = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    s = jnp.where(a > 0, a / 7.0, 1.0)
    # --- the old gpt2._kv_quantize int4 body, verbatim ---
    q_old = jnp.clip(jnp.round(x32 / s), -7, 7).astype(jnp.int32) + 8
    half = q_old.shape[-1] // 2
    packed_old = (q_old[..., :half] << 4 | q_old[..., half:]).astype(jnp.uint8)
    # --- the old gpt2._unpack_int4 body, verbatim ---
    hi_old = (packed_old >> 4).astype(jnp.int8) - 8
    lo_old = (packed_old & 0xF).astype(jnp.int8) - 8
    unpacked_old = jnp.concatenate([hi_old, lo_old], axis=-1)

    packed_new = pack_int4(jnp.clip(jnp.round(x32 / s), -7, 7))
    np.testing.assert_array_equal(np.asarray(packed_new), np.asarray(packed_old))
    np.testing.assert_array_equal(
        np.asarray(unpack_int4(packed_new)), np.asarray(unpacked_old)
    )
    # and the live model path still produces the same packed cache
    import dataclasses

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config

    model = GPT2(dataclasses.replace(GPT2Config.tiny(), kv_quant="int4"))
    kq, ks = model._kv_quantize(x32)
    np.testing.assert_array_equal(np.asarray(kq), np.asarray(packed_old))
    np.testing.assert_array_equal(
        np.asarray(model._unpack_int4(kq)), np.asarray(unpacked_old)
    )


def test_pack_int4_rejects_odd_axis():
    with pytest.raises(ValueError, match="even"):
        pack_int4(jnp.zeros((4, 3), jnp.int32))


def test_quantize_roundtrip_error_bounded_by_quantum():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal(1000) * 3.0, jnp.float32)
    for scheme in ("int8", "int4"):
        back = quantize_roundtrip(x, scheme)
        qmax = get_scheme(scheme).qmax
        # deterministic nearest rounding: error ≤ half a quantum per block
        assert np.abs(np.asarray(back - x)).max() <= (
            float(jnp.abs(x).max()) / qmax / 2 + 1e-6
        )


def test_env_knobs_qblock_and_quant(monkeypatch):
    monkeypatch.setenv("DSML_QBLOCK", "256")
    assert default_qblock() == 256
    assert get_scheme("int8").block == 256
    for bad in ("0", "-4", "511", "nope"):
        monkeypatch.setenv("DSML_QBLOCK", bad)
        assert default_qblock() == 512
    monkeypatch.delenv("DSML_QBLOCK", raising=False)

    monkeypatch.delenv("DSML_QUANT", raising=False)
    assert quant_algorithm_for("float32") == "q8_ring2"  # documented default
    monkeypatch.setenv("DSML_QUANT", "int4:ring")
    assert quant_algorithm_for("float32") == "q4_ring"
    monkeypatch.setenv("DSML_QUANT", "none")
    assert quant_algorithm_for("float32") == "ring2"
    monkeypatch.setenv("DSML_QUANT", "float32=int8:ring,bfloat16=int4:ring2")
    assert quant_algorithm_for("float32") == "q8_ring"
    assert quant_algorithm_for(jnp.bfloat16) == "q4_ring2"
    monkeypatch.setenv("DSML_QUANT", "bfloat16=int4,default=int8:ring2")
    assert quant_algorithm_for("float64") == "q8_ring2"
    monkeypatch.setenv("DSML_QUANT", "garbage:value")
    assert quant_algorithm_for("float32") == "q8_ring2"  # loud fallback > crash


def test_get_scheme_validation():
    with pytest.raises(ValueError, match="unknown quant scheme"):
        get_scheme("int2")
    with pytest.raises(ValueError, match="even"):
        get_scheme("int4", block=3)
    sch = get_scheme("int8", block=128)
    assert (sch.bits, sch.qmax, sch.block) == (8, 127, 128)
    assert sch.wire_bytes_per_block == 128 + 4
    assert get_scheme(sch) is sch


@pytest.mark.parametrize("grid", [(2, 4), (4, 2)])
@pytest.mark.parametrize("op", [ReduceOp.SUM, ReduceOp.AVG, ReduceOp.MAX, ReduceOp.PROD])
def test_hierarchical_all_reduce_matches_flat(devices8, grid, op):
    n_outer, n_inner = grid
    mesh = Mesh(np.asarray(devices8).reshape(n_outer, n_inner), ("o", "i"))
    rng = np.random.default_rng(4)
    # 1000 elements: NOT divisible by n_inner → exercises identity padding
    x = rng.uniform(0.5, 1.5, size=(8, 1000)).astype(np.float32)

    def flat_ref(op):
        if op == ReduceOp.SUM:
            return x.sum(axis=0)
        if op == ReduceOp.AVG:
            return x.mean(axis=0)
        if op == ReduceOp.MAX:
            return x.max(axis=0)
        return np.prod(x, axis=0)

    got = jax.jit(
        jax.shard_map(
            lambda s: hierarchical_all_reduce(s[0, 0], "i", "o", op)[None, None],
            mesh=mesh,
            in_specs=P("o", "i"),
            out_specs=P("o", "i"),
            check_vma=False,
        )
    )(jnp.asarray(x).reshape(n_outer, n_inner, 1000))
    got0 = np.asarray(got).reshape(8, 1000)[0]
    np.testing.assert_allclose(got0, flat_ref(op), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# block-quantized weights + the dequant-fused Pallas matmul (DSML_WEIGHT_QUANT)
# ---------------------------------------------------------------------------


def test_blocked_weight_kernel_matches_dequant_oracle():
    """The fused matmul vs ``x @ dequantize_weight_blocks`` — the XLA
    fallback IS the oracle, so relative error is float-reassociation
    noise only, across both codecs, odd shapes, and the 3-D wqkv form."""
    from dsml_tpu.ops.quantization import (
        dequantize_weight_blocks, quantize_weight_blocks, quantized_matmul,
    )

    rng = np.random.default_rng(0)
    for scheme in ("int8", "int4"):
        for (m, d, n), block in [((3, 64, 48), 512), ((7, 200, 130), 64),
                                 ((16, 512, 256), 128)]:
            w = jnp.asarray(rng.standard_normal((d, n)), jnp.float32)
            x = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
            qwt = quantize_weight_blocks(w, scheme, block)
            deq = dequantize_weight_blocks(qwt)
            assert deq.shape == (d, n)
            got = np.asarray(quantized_matmul(x, qwt))
            ref = np.asarray(x @ deq)
            err = np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-9)
            assert got.shape == (m, n)
            assert err < 1e-5, (scheme, m, d, n, block, err)
    # 3-D weight (GPT-2's fused wqkv): trailing axes flatten to columns
    w3 = jnp.asarray(rng.standard_normal((64, 3, 32)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    qwt = quantize_weight_blocks(w3, "int4", 64)
    deq = dequantize_weight_blocks(qwt)
    assert deq.shape == (64, 3, 32)
    np.testing.assert_allclose(
        np.asarray(quantized_matmul(x, qwt)),
        np.asarray(x @ np.asarray(deq).reshape(64, -1)),
        rtol=1e-5, atol=1e-4)


def test_blocked_weight_kernel_integer_exact():
    """On codec-representable integer weights (every (block, column)
    absmax pinned to qmax so scales are exactly 1) with small-integer
    activations, the kernel is EXACT — scale folding after the dot loses
    nothing the codec kept."""
    from dsml_tpu.ops.quantization import quantize_weight_blocks, quantized_matmul

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(-8, 9, (5, 96)), jnp.float32)
    w = jnp.asarray(rng.integers(-127, 128, (96, 160)), jnp.float32)
    w = w.at[0::32, :].set(127.0)  # absmax per (block, column) -> scale 1
    got = np.asarray(quantized_matmul(x, quantize_weight_blocks(w, "int8", 32)))
    assert np.array_equal(got, np.asarray(x @ w))

    w4 = jnp.asarray(rng.integers(-7, 8, (96, 128)), jnp.float32)
    w4 = w4.at[0::32, :].set(7.0)
    got = np.asarray(quantized_matmul(x, quantize_weight_blocks(w4, "int4", 32)))
    assert np.array_equal(got, np.asarray(x @ w4))


def test_blocked_weight_compression_floors():
    """HBM bytes vs the dense f32 leaf at real model dims (d=768): the
    k-block divisor rule must not round 768 up to a block multiple — the
    acceptance floors are 3.9x (int8) and 7.8x (int4)."""
    from dsml_tpu.ops.quantization import quantize_weight_blocks

    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal((768, 768)), jnp.float32)
    for scheme, floor in (("int8", 3.9), ("int4", 7.8)):
        qwt = quantize_weight_blocks(w, scheme)
        assert qwt.dense_bytes / qwt.hbm_bytes >= floor
    # quant error bounded by the codec quantum
    from dsml_tpu.ops.quantization import dequantize_weight_blocks

    q8 = np.asarray(dequantize_weight_blocks(quantize_weight_blocks(w, "int8")))
    lim = float(jnp.max(jnp.abs(w))) / 127 * 0.51 * 2
    assert np.max(np.abs(q8 - np.asarray(w))) <= lim


def test_weight_quant_mode_env_knob(monkeypatch):
    from dsml_tpu.ops.quantization import weight_quant_mode

    monkeypatch.delenv("DSML_WEIGHT_QUANT", raising=False)
    assert weight_quant_mode() is None
    for raw, want in [("int8", "int8"), ("8", "int8"), ("int4", "int4"),
                      ("4", "int4"), (" INT4 ", "int4"), ("fp8", None),
                      ("", None)]:
        monkeypatch.setenv("DSML_WEIGHT_QUANT", raw)
        assert weight_quant_mode() == want, raw


def test_blocked_weight_batcher_tokens_and_ledger():
    """The serving wire-through: ``ContinuousBatcher(weight_quant=...)``
    quantizes at admission, serves token-exactly vs ``generate`` on the
    same quantized params, and claims packed+scales bytes under the
    ledger's ``weights_quant`` subsystem at >=3.9x/7.8x compression
    (d_model=768 — the floors are stated at real dims)."""
    from dsml_tpu.models.common import quantize_weights_blocked
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.obs.memory import get_memory_ledger
    from dsml_tpu.ops.quantization import QuantizedWeight
    from dsml_tpu.serving import ContinuousBatcher

    cfg = GPT2Config(vocab_size=512, max_seq=64, n_layer=1, n_head=4,
                     d_model=768, d_ff=3072)
    model = GPT2(cfg)
    params = model.init(7)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 512, 10)
    for scheme, floor in (("int8", 3.9), ("int4", 7.8)):
        srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(16,),
                                weight_quant=scheme)
        assert srv.weight_quant == scheme
        rid = srv.submit(prompt, 3)
        toks = srv.run()[rid]
        ref = model.generate(quantize_weights_blocked(params, scheme),
                             jnp.asarray(prompt)[None], 3)[0]
        assert toks == np.asarray(ref).tolist()
        wq = get_memory_ledger(srv._obs).claimed()["weights_quant"]
        assert set(wq) == {"packed", "scales"} and wq["scales"] > 0
        dense = sum(
            l.dense_bytes for l in jax.tree.leaves(
                srv.params, is_leaf=lambda l: isinstance(l, QuantizedWeight))
            if isinstance(l, QuantizedWeight))
        assert dense / sum(wq.values()) >= floor
    # off stays off; TP meshes are rejected (param_specs expect plain leaves)
    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(16,),
                            weight_quant=None)
    assert srv.weight_quant is None and not srv._wq_bytes
    with pytest.raises(ValueError, match="weight_quant"):
        ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(16,),
                          weight_quant="fp8")


def test_blocked_weight_matmul_vmem_fallback(monkeypatch):
    """A starved VMEM budget refuses the fused matmul with a ValueError
    naming the block geometry, the estimate and the budget — it does not
    give way to an XLA dequantize-then-dot."""
    from dsml_tpu.ops import vmem_budget
    from dsml_tpu.ops.quantization import (
        quantize_weight_blocks, quantized_matmul, quantized_matmul_vmem_bytes,
    )

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    qwt = quantize_weight_blocks(w, "int4", 128)
    monkeypatch.setattr(vmem_budget, "_DEFAULT_VMEM_BYTES", 16 * 1024)
    monkeypatch.delenv("DSML_VMEM_LIMIT_MB", raising=False)
    need = quantized_matmul_vmem_bytes(8, 128, 128, True)
    with pytest.raises(ValueError, match=rf"8x128x128 \(int4\).*{need} B"
                       rf".*{int(16 * 1024 * 0.9)} B"):
        quantized_matmul(x, qwt)
