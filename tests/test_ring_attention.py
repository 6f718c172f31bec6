"""Context-parallel ring attention (``ops/ring_attention.py``) vs the
single-device reference — the ISSUE 12 acceptance pins.

Forward AND backward parity to single-device attention at cp ∈ {2, 4},
causal and non-causal, odd per-rank lengths included (the flash kernel's
padded path owns residual blocks); the shared ``ring_pass`` rotate step;
exact KV wire-byte counting; the ``attn_impl="ring2"`` route through GPT-2
and the hybrid step's cp composition with dp/fsdp.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dsml_tpu.ops.attention import attention
from dsml_tpu.ops.ring_attention import (
    causal_critical_path_fraction,
    causal_keep_fraction,
    ring_attention,
    ring_kv_wire_bytes,
    zigzag_indices,
    zigzag_inverse,
)


def _cp_mesh(devices8, cp):
    return Mesh(np.asarray(devices8[:cp]).reshape(cp), ("cp",))


def _qkv(s, d=16, h=2, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((1, h, s, d)), jnp.float32) for _ in range(3)]


def _ring_fn(mesh, causal):
    spec = P(None, None, "cp", None)
    return jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "cp", causal),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
        )
    )


# cp ∈ {2, 4} × causal × odd lengths: 66/2 = 33 and 52/4 = 13 rows per rank
# are NOT multiples of any flash block — the padded-kernel path is load-
# bearing here, exactly as it is for real cp shards of odd ladders
@pytest.mark.parametrize("cp,s", [(2, 64), (2, 66), (2, 10), (4, 96), (4, 52)])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_forward_matches_full_attention(devices8, cp, s, causal):
    q, k, v = _qkv(s, seed=cp * 100 + s)
    got = np.asarray(_ring_fn(_cp_mesh(devices8, cp), causal)(q, k, v))
    expected = np.asarray(attention(q, k, v, causal))
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("cp,s", [(2, 66), (4, 96), (4, 52)])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_backward_matches_full_attention(devices8, cp, s, causal):
    """The KV re-streaming backward: dq accumulated locally, dk/dv toured
    around the reverse ring back to their owners — must equal the dense
    reference's gradients for ALL THREE operands."""
    q, k, v = _qkv(s, seed=7)
    fn = _ring_fn(_cp_mesh(devices8, cp), causal)
    w = jnp.cos(jnp.arange(q.shape[-1]))

    grads = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2)
    ))(q, k, v)
    ref = jax.grad(
        lambda q, k, v: jnp.sum(attention(q, k, v, causal) * w), argnums=(0, 1, 2)
    )(q, k, v)
    for g, r in zip(grads, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-3, atol=2e-4)


def test_ring_matches_flash_lse_merge_semantics(devices8):
    """bf16 inputs keep bf16 outputs and stay within bf16 tolerance of the
    f32 dense reference (the merge runs f32 internally)."""
    q, k, v = _qkv(64, seed=3)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = _ring_fn(_cp_mesh(devices8, 4), True)(qb, kb, vb)
    assert out.dtype == jnp.bfloat16
    expected = attention(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected), rtol=5e-2, atol=5e-2
    )


def test_ring_pass_rotates_both_directions(mesh8):
    from dsml_tpu.ops.collectives import ring_pass

    vals = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)

    def body(x):
        fwd = ring_pass(x, "dev", +1)
        bwd = ring_pass(x, "dev", -1)
        both = ring_pass((x, x), "dev", +1)  # pytree leaves rotate together
        return fwd, bwd, both[0]

    fwd, bwd, tree = jax.jit(jax.shard_map(
        body, mesh=mesh8, in_specs=P("dev"), out_specs=P("dev"), check_vma=False
    ))(vals)
    np.testing.assert_array_equal(np.asarray(fwd).ravel(), np.roll(np.arange(8), 1))
    np.testing.assert_array_equal(np.asarray(bwd).ravel(), np.roll(np.arange(8), -1))
    np.testing.assert_array_equal(np.asarray(tree), np.asarray(fwd))


def test_ring_pass_rejects_bad_sign(mesh8):
    from dsml_tpu.ops.collectives import ring_pass

    with pytest.raises(ValueError, match="sign"):
        jax.jit(jax.shard_map(
            lambda x: ring_pass(x, "dev", 2),
            mesh=mesh8, in_specs=P("dev"), out_specs=P("dev"), check_vma=False,
        ))(jnp.zeros((8,)))


def test_ring_perm_tables_shared_by_all_ring_schedules():
    """The satellite: ONE perm-table definition. The quantized ring's
    private helper must BE the collectives table, not a drifted copy."""
    from dsml_tpu.ops.collectives import ring_perm_tables
    from dsml_tpu.ops.quantization import _ring_perms

    assert _ring_perms(8) == ring_perm_tables(8)
    assert ring_perm_tables(4) == {
        +1: [(0, 1), (1, 2), (2, 3), (3, 0)],
        -1: [(0, 3), (1, 0), (2, 1), (3, 2)],
    }


def test_ring_kv_wire_bytes_exact_counting():
    """Exact, not sampled: cross-check the counting model by hand.
    s_local=128, n=4, h=2, hd=16, f32 — per hop both directions together
    carry the full resident shard (K+V): 2·(1·2·128·16)·4 bytes."""
    shard_kv_bytes = 2 * (1 * 2 * 128 * 16) * 4
    fwd = ring_kv_wire_bytes(128, 4, 2, 16)
    assert fwd == 3 * shard_kv_bytes  # n−1 hops
    # unidirectional moves the same TOTAL volume (the bidirectional split
    # halves per-LINK volume on full-duplex ICI, not the byte count)
    assert fwd == ring_kv_wire_bytes(128, 4, 2, 16, bidirectional=False)
    # backward: re-stream K/V + f32 dk/dv riding along + one homing hop
    bwd = ring_kv_wire_bytes(128, 4, 2, 16, backward=True)
    assert bwd == 3 * (shard_kv_bytes + shard_kv_bytes) + shard_kv_bytes
    # odd shard length: halves 5/4 still tile the shard exactly
    assert ring_kv_wire_bytes(9, 2, 1, 8) == 1 * 2 * (1 * 1 * 9 * 8) * 4
    assert ring_kv_wire_bytes(128, 1, 2, 16) == 0


def test_causal_keep_fraction():
    """(n+1)/2n of the hop grid executes under causal skipping — rank r
    runs r+1 forward and 1+r backward hops of n each."""
    assert causal_keep_fraction(1) == 1.0
    assert causal_keep_fraction(2) == 0.75
    assert causal_keep_fraction(8) == pytest.approx(9 / 16)
    # asymptotically the causal-mask 2×
    assert causal_keep_fraction(1024) == pytest.approx(0.5, abs=1e-3)


# ---------------------------------------------------------------------------
# zigzag/striped shard ordering (the causal load-balance fix)
# ---------------------------------------------------------------------------


def _zigzag_fn(mesh, causal):
    spec = P(None, None, "cp", None)
    return jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "cp", causal,
                                           layout="zigzag"),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
        )
    )


def test_zigzag_permutation_places_paired_stripes():
    """Rank r gets stripes {r, 2n−1−r}: an early stripe paired with a
    late one, and the inverse restores global order exactly."""
    perm = zigzag_indices(2, 8)  # stripe=2: r0 → {0,3}, r1 → {1,2}
    np.testing.assert_array_equal(perm, [0, 1, 6, 7, 2, 3, 4, 5])
    inv = zigzag_inverse(2, 8)
    np.testing.assert_array_equal(perm[inv], np.arange(8))
    np.testing.assert_array_equal(inv[perm], np.arange(8))
    with pytest.raises(ValueError, match="2·cp stripes"):
        zigzag_indices(2, 10)


# parity at cp ∈ {2, 4}, causal AND non-causal, including a per-rank
# length (2·13=26 rows at cp=2... 52/2) whose stripes are odd flash blocks
@pytest.mark.parametrize("cp,s", [(2, 64), (2, 52), (4, 96), (4, 104)])
@pytest.mark.parametrize("causal", [True, False])
def test_zigzag_forward_matches_full_attention(devices8, cp, s, causal):
    """The satellite pin: zigzag-sharded ring attention ≡ dense attention
    after un-permuting — causal skipping now predicates per stripe pair,
    and the answer must not move."""
    q, k, v = _qkv(s, seed=cp * 10 + s)
    perm, inv = zigzag_indices(cp, s), zigzag_inverse(cp, s)
    fn = _zigzag_fn(_cp_mesh(devices8, cp), causal)
    got = np.asarray(
        fn(q[:, :, perm], k[:, :, perm], v[:, :, perm])[:, :, inv]
    )
    expected = np.asarray(attention(q, k, v, causal))
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("cp,s", [(2, 64), (4, 96)])
def test_zigzag_backward_matches_full_attention(devices8, cp, s):
    """Gradients through the stripe-blocked backward (dq per stripe,
    dk/dv touring the ring) equal the dense reference's for all three
    operands."""
    q, k, v = _qkv(s, seed=17)
    perm, inv = zigzag_indices(cp, s), zigzag_inverse(cp, s)
    fn = _zigzag_fn(_cp_mesh(devices8, cp), True)
    w = jnp.cos(jnp.arange(q.shape[-1]))

    def loss(q, k, v):
        out = fn(q[:, :, perm], k[:, :, perm], v[:, :, perm])[:, :, inv]
        return jnp.sum(out * w)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    ref = jax.grad(
        lambda q, k, v: jnp.sum(attention(q, k, v, True) * w),
        argnums=(0, 1, 2),
    )(q, k, v)
    for g, r in zip(grads, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-3, atol=2e-4)


def test_zigzag_validation(devices8):
    with pytest.raises(ValueError, match="layout"):
        ring_attention(jnp.zeros((1, 2, 8, 16)), jnp.zeros((1, 2, 8, 16)),
                       jnp.zeros((1, 2, 8, 16)), "cp", layout="striped")
    spec = P(None, None, "cp", None)
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "cp", True, layout="zigzag"),
        mesh=_cp_mesh(devices8, 2), in_specs=(spec,) * 3, out_specs=spec,
        check_vma=False,
    ))
    with pytest.raises(ValueError, match="even per-rank"):
        fn(*_qkv(10))  # 5 rows per rank: stripes can't split evenly


def test_zigzag_keep_fraction_and_critical_path():
    """The load-balance arithmetic: zigzag keeps the SAME asymptotic mean
    ((2n+1)/4n → ½) but makes it constant per rank, so the critical path
    drops from 1.0 (contiguous rank n−1 runs everything) to the mean —
    the ~2× wall win at large cp."""
    for n, frac in ((2, 5 / 8), (4, 9 / 16)):
        assert causal_keep_fraction(n, "zigzag") == pytest.approx(frac)
        # constant per-rank work ⇒ critical path IS the mean
        assert causal_critical_path_fraction(n, "zigzag") == \
            causal_keep_fraction(n, "zigzag")
        # contiguous: same-ish mean, but the LAST rank runs its whole grid
        assert causal_critical_path_fraction(n, "contiguous") == 1.0
        assert causal_critical_path_fraction(n, "zigzag") < 1.0
    assert causal_keep_fraction(1, "zigzag") == 1.0
    assert causal_critical_path_fraction(1) == 1.0
    # asymptotics: both layouts' means → the causal-mask 2×
    assert causal_keep_fraction(1024, "zigzag") == pytest.approx(0.5, abs=1e-3)
    # non-causal executes everything either way (layout is causal-only
    # load balancing; parity pinned above)
    assert causal_keep_fraction(1024) == pytest.approx(0.5, abs=1e-3)


def test_gpt2_ring2_loss_matches_ring_on_cp_mesh(devices8):
    """attn_impl='ring2' through the model on a cp mesh: same loss as the
    exact XLA ring — per-rank positions offset by the cp shard origin, the
    sequence-parallel chunked-xent loss never assembles full logits."""
    from jax import lax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.parallel.hybrid import hybrid_loss_fn, shard_params
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    model = GPT2(GPT2Config.tiny())
    params = model.init(9)
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.integers(0, 512, (4, 128)), jnp.int32)
    y = jnp.roll(x, -1, 1)
    mesh = build_mesh(MeshSpec(dp=2, cp=4), devices8)
    placed = shard_params(params, mesh, model.param_specs())

    def run(impl):
        fn = jax.jit(jax.shard_map(
            lambda p, xx, yy: lax.pmean(
                hybrid_loss_fn(model, impl, seq_axis="cp")(p, xx, yy), ("dp", "cp")
            ),
            mesh=mesh,
            in_specs=(model.param_specs(), P("dp", "cp"), P("dp", "cp")),
            out_specs=P(),
            check_vma=False,
        ))
        return float(fn(placed, x, y))

    assert np.isclose(run("ring2"), run("ring"), rtol=1e-4)


def test_gpt2_ring2_degenerates_to_flash_without_seq_axis():
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config

    model = GPT2(GPT2Config.tiny())
    params = model.init(0)
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, 512, size=(2, 128)), jnp.int32)
    base = model.apply_spmd(params, tokens, attn_impl="xla")
    ring2 = model.apply_spmd(params, tokens, attn_impl="ring2")
    np.testing.assert_allclose(np.asarray(ring2), np.asarray(base), rtol=1e-4, atol=1e-4)


def test_hybrid_cp_train_step_matches_single_device(devices8):
    """THE composition pin: a cp=4 × dp=2 hybrid train step (attn_impl
    auto-resolves to ring2) tracks the single-device step's loss through
    multiple optimizer updates — cp composes with dp like sp does."""
    import optax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.parallel.hybrid import init_hybrid, make_hybrid_train_step
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    model = GPT2(GPT2Config.tiny())
    opt = optax.adam(1e-3)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 512, (4, 128)), jnp.int32)
    y = jnp.roll(x, -1, 1)

    mesh1 = build_mesh(MeshSpec(dp=1), devices8[:1])
    p1, o1 = init_hybrid(model, opt, mesh1, seed=3)
    step1 = make_hybrid_train_step(model, opt, mesh1)

    mesh = build_mesh(MeshSpec(dp=2, cp=4), devices8)
    p, o = init_hybrid(model, opt, mesh, seed=3)
    step = make_hybrid_train_step(model, opt, mesh)

    for _ in range(3):
        p1, o1, l1 = step1(p1, o1, x, y)
        p, o, l = step(p, o, x, y)
        assert np.isclose(float(l), float(l1), rtol=1e-3), (float(l), float(l1))


def test_hybrid_cp_composes_with_fsdp(devices8):
    import optax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.parallel.hybrid import init_hybrid, make_hybrid_train_step
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    model = GPT2(GPT2Config.tiny())
    opt = optax.adam(1e-3)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, 512, (2, 128)), jnp.int32)
    y = jnp.roll(x, -1, 1)

    mesh = build_mesh(MeshSpec(dp=1, fsdp=2, cp=4), devices8)
    p, o = init_hybrid(model, opt, mesh, seed=3)
    step = make_hybrid_train_step(model, opt, mesh)
    p, o, loss = step(p, o, x, y)
    assert np.isfinite(float(loss))

    mesh1 = build_mesh(MeshSpec(dp=1), devices8[:1])
    p1, o1 = init_hybrid(model, opt, mesh1, seed=3)
    _, _, l1 = make_hybrid_train_step(model, opt, mesh1)(p1, o1, x, y)
    assert np.isclose(float(loss), float(l1), rtol=2e-4)


def test_sp_and_cp_both_sized_rejected(devices8):
    import optax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.parallel.hybrid import make_hybrid_train_step
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    with pytest.raises(ValueError, match="ONE sequence"):
        MeshSpec(sp=2, cp=2).seq_axis()
    mesh = build_mesh(MeshSpec(dp=2, sp=2, cp=2), devices8)
    with pytest.raises(ValueError, match="ONE sequence"):
        make_hybrid_train_step(GPT2(GPT2Config.tiny()), optax.adam(1e-3), mesh)


def test_llama_ring2_loss_matches_ring_on_cp_mesh(devices8):
    """Second family: Llama's RoPE positions derive from the cp shard
    origin exactly as from sp — ring2 ≡ ring on a cp mesh."""
    from jax import lax

    from dsml_tpu.models.llama import Llama, LlamaConfig
    from dsml_tpu.parallel.hybrid import hybrid_loss_fn, shard_params
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    model = Llama(LlamaConfig.tiny())
    params = model.init(2)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.integers(0, model.config.vocab_size, (4, 128)), jnp.int32)
    y = jnp.roll(x, -1, 1)
    mesh = build_mesh(MeshSpec(dp=2, cp=4), devices8)
    placed = shard_params(params, mesh, model.param_specs())

    def run(impl):
        fn = jax.jit(jax.shard_map(
            lambda p, xx, yy: lax.pmean(
                hybrid_loss_fn(model, impl, seq_axis="cp")(p, xx, yy), ("dp", "cp")
            ),
            mesh=mesh,
            in_specs=(model.param_specs(), P("dp", "cp"), P("dp", "cp")),
            out_specs=P(),
            check_vma=False,
        ))
        return float(fn(placed, x, y))

    assert np.isclose(run("ring2"), run("ring"), rtol=1e-4)


# ---------------------------------------------------------------------------
# fused KV-hop schedules (DSML_RING_FUSED): oracle ≡ sendahead ≡ dma
# ---------------------------------------------------------------------------


def _fused_fn(mesh, causal, fused, layout="contiguous"):
    spec = P(None, None, "cp", None)
    return jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "cp", causal,
                                           layout=layout, fused=fused),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
        )
    )


def test_ring_fused_mode_env_knob(monkeypatch):
    from dsml_tpu.ops.ring_attention import ring_fused_mode

    monkeypatch.delenv("DSML_RING_FUSED", raising=False)
    assert ring_fused_mode() is None
    for raw, want in [("0", None), ("off", None), ("1", "sendahead"),
                      ("on", "sendahead"), ("auto", "sendahead"),
                      ("sendahead", "sendahead"), ("DMA ", "dma")]:
        monkeypatch.setenv("DSML_RING_FUSED", raw)
        assert ring_fused_mode() == want, raw
    # the public entry rejects junk instead of silently de-fusing
    with pytest.raises(ValueError, match="fused"):
        ring_attention(jnp.zeros((1, 1, 8, 8)), jnp.zeros((1, 1, 8, 8)),
                       jnp.zeros((1, 1, 8, 8)), "cp", fused="bogus")


# odd per-rank rows (66/2=33, 52/4=13) keep the padded flash path load-
# bearing inside the streamed hop too. The causal legs are the acceptance
# pin (both modes × both cp in the default tier); the non-causal matrix
# rides in the slow tier — hop scheduling is mask-independent, so the
# causal legs already exercise every fused code path.
@pytest.mark.parametrize("cp,s", [(2, 66), (4, 52)])
@pytest.mark.parametrize(
    "causal", [True, pytest.param(False, marks=pytest.mark.slow)])
@pytest.mark.parametrize("fused", ["sendahead", "dma"])
def test_ring_fused_forward_bit_identical(devices8, cp, s, causal, fused):
    """All three hop schedules perform the SAME merges in the SAME order
    — fused forwards are bit-identical to the XLA-ppermute oracle, not
    merely close (the acceptance pin that makes the oracle an oracle)."""
    q, k, v = _qkv(s, seed=cp * 7 + s)
    mesh = _cp_mesh(devices8, cp)
    want = np.asarray(_fused_fn(mesh, causal, None)(q, k, v))
    got = np.asarray(_fused_fn(mesh, causal, fused)(q, k, v))
    assert np.array_equal(got, want)
    np.testing.assert_allclose(
        got, np.asarray(attention(q, k, v, causal)), rtol=2e-4, atol=2e-5)


# default tier keeps cp ∈ {2,4} with the two modes split across them
# (the acceptance pin); the transposed mode×cp pairings are the slow-tier
# half of the matrix — the backward schedule differs by mode, not by cp
@pytest.mark.parametrize("cp,s,fused", [
    (2, 66, "sendahead"),
    (4, 52, "dma"),
    pytest.param(2, 66, "dma", marks=pytest.mark.slow),
    pytest.param(4, 52, "sendahead", marks=pytest.mark.slow),
])
def test_ring_fused_backward_parity(devices8, cp, s, fused):
    """Loss/grad parity: the fused backward rotates the kv legs ahead of
    compute and homes the dk/dv accumulators after it — gradients match
    the oracle schedule and the dense reference."""
    q, k, v = _qkv(s, seed=cp * 31 + s)
    mesh = _cp_mesh(devices8, cp)

    def loss(fn):
        return jax.grad(
            lambda args: jnp.sum(jnp.tanh(fn(*args))), allow_int=False
        )((q, k, v))

    g_want = loss(_fused_fn(mesh, True, None))
    g_got = loss(_fused_fn(mesh, True, fused))
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    g_dense = jax.grad(
        lambda args: jnp.sum(jnp.tanh(attention(*args, True)))
    )((q, k, v))
    for a, b in zip(g_got, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize(
    "fused", ["sendahead", pytest.param("dma", marks=pytest.mark.slow)])
def test_ring_fused_zigzag_composes(devices8, fused):
    """The causal load-balance layout and the fused hop are orthogonal:
    zigzag + fused ≡ zigzag + oracle, bit for bit."""
    cp, s = 4, 96
    q, k, v = _qkv(s, seed=99)
    perm = zigzag_indices(cp, s)
    inv = zigzag_inverse(cp, s)
    mesh = _cp_mesh(devices8, cp)
    args = [t[:, :, perm, :] for t in (q, k, v)]
    want = np.asarray(_fused_fn(mesh, True, None, "zigzag")(*args))
    got = np.asarray(_fused_fn(mesh, True, fused, "zigzag")(*args))
    assert np.array_equal(got, want)
    np.testing.assert_allclose(
        np.asarray(got)[:, :, inv, :], np.asarray(attention(q, k, v, True)),
        rtol=2e-4, atol=2e-5)
