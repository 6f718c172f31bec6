"""Pallas flash-attention kernel vs the plain fused-XLA reference.

Runs on the CI CPU mesh via the Pallas interpreter (``interpret=True`` is
the default off-TPU); on TPU the same kernels compile through Mosaic —
``tests/test_tpu_compile.py`` asks the compiler, ``benchmarks/`` runs them.
Forward AND the custom-VJP backward (dq/dk/dv flash kernels) must agree
with ``attention`` to float32 tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dsml_tpu.ops.attention import attention
from dsml_tpu.ops.flash import flash_attention


def _qkv(b=2, h=3, s=128, d=32, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32) for _ in range(3)]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq", [128, 192])  # 192 exercises the 64-block tiling
def test_flash_forward_matches_attention(causal, seq):
    q, k, v = _qkv(s=seq)
    expected = np.asarray(attention(q, k, v, causal))
    got = np.asarray(flash_attention(q, k, v, causal))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_attention(causal):
    q, k, v = _qkv(s=128, seed=1)
    w = jnp.cos(jnp.arange(q.shape[-1]))  # non-uniform cotangent

    flash_grads = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, causal) * w).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    ref_grads = jax.grad(
        lambda q, k, v: (attention(q, k, v, causal) * w).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for got, expected in zip(flash_grads, ref_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dk,dv", [(48, 32), (192, 128), (32, 48)])
@pytest.mark.parametrize("seq", [64, 70])  # 70 takes the padded path: blocks of 16 over 80 rows, the tail masked
@pytest.mark.parametrize("pair", [False, True], ids=["fused", "pair"])
def test_flash_query_key_width_apart_from_value_width(monkeypatch, dk, dv, seq, pair):
    """Latent attention's shapes: q and k ``dk`` wide, v ``dv``; the output and
    ``dv`` are ``dv`` wide, ``dq`` and ``dk`` ``dk`` wide, the scale ``dk ** -0.5``;
    causal, forward and the three gradients against dense attention, with ``dq``
    riding ``flash_dkv`` and, with no VMEM to give (a query too long for the
    fused backward), through ``flash_dq`` beside it."""
    from dsml_tpu.ops import flash as kernels

    if pair:
        monkeypatch.setattr(kernels, "_VMEM_BUDGET", 0)
    rng = np.random.default_rng(dk + dv + seq)
    q, k = (jnp.asarray(rng.standard_normal((1, 2, seq, dk)), jnp.float32) for _ in range(2))
    v, w = (jnp.asarray(rng.standard_normal((1, 2, seq, dv)), jnp.float32) for _ in range(2))

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(dk)
        s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16, block_k=16)

    def loss(f):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(f(q, k, v) * w), (0, 1, 2))

    assert _bwd_kernels(loss(flash), q, k, v) == ["flash_fwd"] + (["flash_dq"] if pair else []) + ["flash_dkv"]
    got, want = (jax.jit(loss(f))(q, k, v) for f in (flash, dense))
    assert jax.eval_shape(flash, q, k, v).shape == (1, 2, seq, dv)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, e in zip(got[1], want[1]):
        assert g.shape == e.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-4, atol=1e-4)


def test_flash_jits_and_handles_bf16():
    q, k, v = _qkv(s=128, seed=2)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))(q, k, v)
    assert out.dtype == jnp.bfloat16
    expected = attention(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected), rtol=5e-2, atol=5e-2
    )


def test_flash_handles_untileable_seq_via_padded_kernel():
    """seq=37 tiles into NO ladder block — it must run through the padded
    kernel path (zero-pad + kv_stop mask), not fall back to the O(s²) XLA
    graph. flash_attention_lse USED to raise here; now it is the proof the
    kernel itself ran (the XLA fallback had no lse output)."""
    from dsml_tpu.ops.flash import flash_attention_lse

    q, k, v = _qkv(s=37, seed=3)
    expected = np.asarray(attention(q, k, v, True))
    got = np.asarray(flash_attention(q, k, v, True))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)
    out, lse = flash_attention_lse(q, k, v, True)
    assert lse.shape == (2, 3, 37)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5, atol=1e-5)


# ring/cp shards make odd residual blocks the COMMON case: lengths that are
# not multiples of block_q/block_k, and S < the smallest block
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq", [5, 37, 100, 515])
def test_flash_odd_length_forward_matches_attention(causal, seq):
    q, k, v = _qkv(s=seq, seed=seq)
    expected = np.asarray(attention(q, k, v, causal))
    got = np.asarray(flash_attention(q, k, v, causal))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq", [5, 37, 100])
def test_flash_odd_length_backward_matches_attention(causal, seq):
    """Backward parity through the padded path: padded q rows carry zero
    cotangents and padded kv columns are kv_stop-masked in BOTH backward
    kernels, so dq/dk/dv must equal the dense reference exactly."""
    q, k, v = _qkv(s=seq, seed=seq + 1)
    w = jnp.cos(jnp.arange(q.shape[-1]))
    flash_grads = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, causal) * w).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    ref_grads = jax.grad(
        lambda q, k, v: (attention(q, k, v, causal) * w).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for got, expected in zip(flash_grads, ref_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=1e-4, atol=1e-4)


def test_flash_odd_mismatched_lengths():
    """s_q ≠ s_kv with BOTH odd (the ring's diagonal-half shape): non-causal
    directly, causal via the q_start offset that aligns sequence ENDS (the
    dense reference's tril(k=s_kv−s_q) convention)."""
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 2, 27, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 53, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 53, 32)), jnp.float32)
    from dsml_tpu.ops.flash import flash_attention_lse

    got, _ = flash_attention_lse(q, k, v, causal=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(attention(q, k, v, False)), rtol=1e-5, atol=1e-5
    )
    got, _ = flash_attention_lse(q, k, v, causal=True, q_start=53 - 27, k_start=0)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(attention(q, k, v, True)), rtol=1e-5, atol=1e-5
    )


def test_flash_odd_length_lse_matches_dense(causal=True):
    from dsml_tpu.ops.flash import flash_attention_lse

    q, k, v = _qkv(s=45, seed=12)
    _, lse = flash_attention_lse(q, k, v, causal)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    scores = jnp.where(jnp.tril(jnp.ones((45, 45), bool)), scores, -1e30)
    expected = jax.scipy.special.logsumexp(scores, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(expected), rtol=1e-5, atol=1e-5)


def test_flash_block_env_override(monkeypatch):
    """DSML_FLASH_BLOCK promotes the hardcoded widening heuristic to a
    tunable: valid values override the auto defaults, explicit arguments
    still win, malformed values degrade to the swept defaults."""
    from dsml_tpu.ops.flash import _default_blocks

    monkeypatch.setenv("DSML_FLASH_BLOCK", "256")
    assert _default_blocks(8192, 8192, None, None, 64) == (256, 256)
    monkeypatch.setenv("DSML_FLASH_BLOCK", "128x512")
    assert _default_blocks(8192, 8192, None, None, 64) == (128, 512)
    # explicit blocks are never second-guessed
    assert _default_blocks(8192, 8192, 1024, None, 64) == (1024, 512)
    # malformed / non-multiple-of-8 → the swept defaults stand
    for bad in ("abc", "0", "12", "-8", "64x"):
        monkeypatch.setenv("DSML_FLASH_BLOCK", bad)
        assert _default_blocks(8192, 8192, None, None, 64) == (1024, 1024)
    monkeypatch.delenv("DSML_FLASH_BLOCK")
    assert _default_blocks(8192, 8192, None, None, 64) == (1024, 1024)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_lse_matches_dense_logsumexp(causal):
    q, k, v = _qkv(s=128, seed=5)
    from dsml_tpu.ops.flash import flash_attention_lse

    out, lse = flash_attention_lse(q, k, v, causal)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((128, 128), bool))
        scores = jnp.where(mask, scores, -1e30)
    expected_lse = jax.scipy.special.logsumexp(scores, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(expected_lse), rtol=1e-5, atol=1e-5)


def test_flash_offsets_shift_causal_mask():
    """With k_start far in the past, a causal call must equal a full
    (unmasked) call; with k_start in the future, output rows are ~uniform
    over nothing visible (lse ≈ floor)."""
    from dsml_tpu.ops.flash import flash_attention_lse

    q, k, v = _qkv(s=64, seed=6)
    past, _ = flash_attention_lse(q, k, v, causal=True, q_start=4096, k_start=0)
    full, _ = flash_attention_lse(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(past), np.asarray(full), rtol=1e-5, atol=1e-5)
    _, lse_future = flash_attention_lse(q, k, v, causal=True, q_start=0, k_start=4096)
    assert float(lse_future.max()) < -1e18  # nothing visible


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_full_attention(mesh8, causal):
    from jax.sharding import PartitionSpec as P

    from dsml_tpu.ops.flash import ring_flash_attention

    rng = np.random.default_rng(7)
    b, h, s, d = 1, 2, 256, 16  # 32 rows per rank over 8 devices
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32) for _ in range(3))
    expected = np.asarray(attention(q, k, v, causal))
    spec = P(None, None, "dev", None)
    got = np.asarray(
        jax.jit(
            jax.shard_map(
                lambda q, k, v: ring_flash_attention(q, k, v, "dev", causal),
                mesh=mesh8, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
            )
        )(q, k, v)
    )
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-5)


def test_ring_flash_gradients_match_full(mesh8):
    from jax.sharding import PartitionSpec as P

    from dsml_tpu.ops.flash import ring_flash_attention

    rng = np.random.default_rng(8)
    b, h, s, d = 1, 2, 256, 16
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32) for _ in range(3))
    spec = P(None, None, "dev", None)

    def ring_loss(q, k, v):
        wrapped = jax.shard_map(
            lambda q, k, v: ring_flash_attention(q, k, v, "dev", True),
            mesh=mesh8, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
        )
        return jnp.sum(wrapped(q, k, v) ** 2)

    grads = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    full = jax.jit(
        jax.grad(lambda q, k, v: jnp.sum(attention(q, k, v, True) ** 2), argnums=(0, 1, 2))
    )(q, k, v)
    for g, r in zip(grads, full):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-3, atol=1e-4)


@pytest.mark.slow
def test_gpt2_ring_flash_loss_matches_ring(devices8):
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.parallel.hybrid import hybrid_loss_fn, shard_params
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(9)
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.integers(0, 512, (4, 128)), jnp.int32)
    y = jnp.roll(x, -1, 1)
    mesh = build_mesh(MeshSpec(dp=2, sp=4, tp=1), devices8)
    placed = shard_params(params, mesh, model.param_specs())

    def run(impl):
        fn = jax.jit(
            jax.shard_map(
                lambda p, xx, yy: lax.pmean(hybrid_loss_fn(model, impl)(p, xx, yy), ("dp", "sp")),
                mesh=mesh,
                in_specs=(model.param_specs(), P("dp", "sp"), P("dp", "sp")),
                out_specs=P(),
                check_vma=False,
            )
        )
        return float(fn(placed, x, y))

    assert np.isclose(run("ring_flash"), run("ring"), rtol=1e-4)


def test_gpt2_flash_attn_impl_matches_default():
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config

    model = GPT2(GPT2Config.tiny())
    params = model.init(0)
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, 512, size=(2, 128)), jnp.int32)
    base = model.apply_spmd(params, tokens, attn_impl="xla")
    flash = model.apply_spmd(params, tokens, attn_impl="flash")
    np.testing.assert_allclose(np.asarray(flash), np.asarray(base), rtol=1e-4, atol=1e-4)


def test_default_blocks_adapt_to_sequence_lengths():
    """The hardware-swept auto defaults adapt q and kv blocks to their own
    lengths: 512 below 4096, 1024 at or above (scripts/flash_block_sweep.py
    measured 1.4x on a v5e at 8k, head_dim 64) — but the 1024 widening is
    GATED on head_dim <= 64 (the swept regime): kernel VMEM scales with
    block x head_dim, and d=128 at 1024-wide blocks could fail compilation
    where the 512 default works. Explicit blocks always win."""
    from dsml_tpu.ops.flash import _default_blocks

    assert _default_blocks(1024, 1024, None, None, 64) == (512, 512)
    assert _default_blocks(2048, 2048, None, None, 64) == (512, 512)
    assert _default_blocks(4096, 4096, None, None, 64) == (1024, 1024)
    assert _default_blocks(8192, 8192, None, None, 64) == (1024, 1024)
    # decode-shaped call: short q against a long cache widens only kv
    assert _default_blocks(512, 8192, None, None, 64) == (512, 1024)
    assert _default_blocks(8192, 8192, 256, 512, 64) == (256, 512)
    assert _default_blocks(8192, 8192, None, 2048, 64) == (1024, 2048)
    # wider heads (or an unknown head_dim) stay at the safe 512
    assert _default_blocks(8192, 8192, None, None, 128) == (512, 512)
    assert _default_blocks(8192, 8192, None, None) == (512, 512)
    # explicit blocks are never second-guessed, whatever the head_dim
    assert _default_blocks(8192, 8192, 1024, 1024, 128) == (1024, 1024)


# ---------------------------------------------------------------------------
# bf16 operands, tile classes, statistic layout (PR 26)
# ---------------------------------------------------------------------------

# One bf16 ulp, relative (8 significant bits) and, for elements near zero,
# absolute: the outputs and all three gradients are STORED in bf16, which
# alone is up to half of this; p and ds rounded to bf16 before their dots
# (2^-9 relative each, averaging over a row's 128+ terms) and the float32
# accumulation order make up the rest. Largest error measured over the six
# cases below: 0.54 ulp of the largest element (3.1e-2 at |dv| 7.4).
_BF16_ULP = 2.0 ** -7


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "seq,d",
    [
        (384, 64),  # 3x3 tiles of 128: three skipped, three crossed, three clear; scale 2^-3 folds into q
        (203, 64),  # odd: padded to 256, kv tail masked (mask_kv); tile (1, 0) clear, (1, 1) crossed twice
        (384, 32),  # scale 32^-0.5 is no power of two: stays on the float32 scores
    ],
)
def test_flash_bf16_matches_float32_reference(causal, seq, d):
    """bf16 inputs go into the MXU as bf16 (float32 accumulation, float32
    statistics): forward and dq/dk/dv against plain attention evaluated in
    float32 on the same bf16-rounded inputs."""
    rng = np.random.default_rng(seq + d)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, seq, d)), jnp.bfloat16) for _ in range(3))
    w = jnp.cos(jnp.arange(d)).astype(jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal, block_q=128, block_k=128)

    def reference(q, k, v):
        return attention(*(t.astype(jnp.float32) for t in (q, k, v)), causal)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    got = (jax.jit(flash)(q, k, v), *jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v))
    expected = (reference(q, k, v), *jax.grad(loss(reference), argnums=(0, 1, 2))(q, k, v))
    for g, e in zip(got, expected):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(e, np.float32), rtol=_BF16_ULP, atol=_BF16_ULP
        )


@pytest.fixture
def fresh_traces():
    """The kernels' calls are jitted (``_flash_fwd``, ``_flash_bwd_calls``), so a
    like call is traced once a process. A test that patches what a kernel body
    reads while it is traced drops the traces before its second run, and the
    ones it leaves behind."""
    yield jax.clear_caches
    jax.clear_caches()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_tile_classes_equal_masked_body(monkeypatch, fresh_traces, dtype):
    """Traced offsets that put a tile in each class — q rows 256..511
    against k columns 128..639 in 128-blocks: (0,0) clear, (0,1) crossed,
    (0,2) and (0,3) skipped, (1,0) and (1,1) clear, (1,2) crossed, (1,3)
    skipped. The mask is the identity on a clear tile, so out, lse and the
    gradients must equal the kernels run with the masked body on every
    tile, and the dense reference."""
    from dsml_tpu.ops import flash

    rng = np.random.default_rng(26)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((1, 2, 512, 64)), dtype) for _ in range(2))

    def run():
        def fn(q, k, v, qs, ks):
            out, lse = flash.flash_attention_lse(q, k, v, True, qs, ks, block_q=128, block_k=128)
            return (out.astype(jnp.float32) * jnp.cos(jnp.arange(64.0))).sum() + lse.sum(), (out, lse)

        grads, (out, lse) = jax.jit(jax.grad(fn, argnums=(0, 1, 2), has_aux=True))(
            q, k, v, jnp.int32(256), jnp.int32(128)
        )
        return [np.asarray(t, np.float32) for t in (out, lse, *grads)]

    by_class = run()

    def every_tile_masked(compute, q0, k0, kv_stop, causal, mask_kv, block_q, block_k, window=None):
        flash.pl.when(flash._seen(q0, k0, block_q))(lambda: compute(True))

    monkeypatch.setattr(flash, "_per_tile_class", every_tile_masked)
    fresh_traces()
    for got, masked in zip(by_class, run()):
        np.testing.assert_allclose(got, masked, rtol=1e-6, atol=1e-6)

    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * 0.125
    seen = (256 + jnp.arange(256))[:, None] >= (128 + jnp.arange(512))[None, :]
    scores = jnp.where(seen, scores, -1e30)
    tol = 1e-5 if dtype == jnp.float32 else _BF16_ULP
    np.testing.assert_allclose(
        by_class[1], np.asarray(jax.scipy.special.logsumexp(scores, axis=-1)), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        by_class[0], np.asarray(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), vf)),
        rtol=tol, atol=tol,
    )


def test_flash_forward_row_chunks_of_a_1024_block():
    """A 1024-row q block is worked 512 rows at a time (the forward only):
    the second chunk's mask starts 512 rows further down."""
    q, k, v = _qkv(b=1, h=1, s=1024, d=64, seed=26)
    got = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, block_q=1024, block_k=512))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(attention(q, k, v, True)), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# the backward in one kernel: dq rides the dkv tile (PR 28)
# ---------------------------------------------------------------------------


def _pallas_calls(fn, *args):
    """``(name, grid)`` of the Pallas calls in ``fn``'s jaxpr, in order."""
    calls = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append((eqn.params["name"], tuple(eqn.params["grid_mapping"].grid)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return calls


def _bwd_kernels(fn, *args):
    """Names of the Pallas calls in ``fn``'s jaxpr, in order."""
    return [name for name, _ in _pallas_calls(fn, *args)]


def _block_grads_both_ways(monkeypatch, q, k, v, causal, q_start=0, k_start=0, block=128):
    """``flash_block_grads`` on the same inputs through the fused kernel (the
    shape rule's choice at these sizes) and, with no VMEM to give, the pair."""
    from dsml_tpu.ops import flash

    out, lse = jax.jit(
        lambda: flash.flash_attention_lse(q, k, v, causal, q_start, k_start, block, block))()
    do = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape).astype(q.dtype)

    def run(kernels):
        def grads(qs, ks):  # offsets traced, as the ring passes them; a fresh function, so nothing cached is reused
            return flash.flash_block_grads(q, k, v, out, lse, do, None, causal, qs, ks, block, block)

        offsets = (jnp.int32(q_start), jnp.int32(k_start))
        assert _bwd_kernels(grads, *offsets) == kernels
        return jax.jit(grads)(*offsets)

    fused = run(["flash_dkv"])
    monkeypatch.setattr(flash, "_VMEM_BUDGET", 0)
    return fused, run(["flash_dq", "flash_dkv"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "seq,d",
    [
        (384, 64),  # 3x3 tiles of 128: a tile of each class (skipped, crossed, clear)
        (203, 64),  # odd: padded to 256, the kv tail masked through mask_kv
        (384, 32),  # the scale does not fold: it stays on the float32 scores and on dk
        (256, 128),  # head 128 (Jamba, Llama): the accumulators are whole lane groups
    ],
)
def test_fused_backward_equals_the_pair(monkeypatch, dtype, causal, seq, d):
    """``dq`` riding the dkv tile: ``dk`` and ``dv`` are the same operations
    in the same order, so equal; ``dq`` sums the same products over the kv
    blocks in the same order through a dot of the other orientation."""
    rng = np.random.default_rng(seq + d)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, seq, d)), dtype) for _ in range(3))
    fused, pair = _block_grads_both_ways(monkeypatch, q, k, v, causal)
    np.testing.assert_array_equal(np.asarray(fused[1]), np.asarray(pair[1]))
    np.testing.assert_array_equal(np.asarray(fused[2]), np.asarray(pair[2]))
    tol = 1e-5 if dtype == jnp.float32 else _BF16_ULP
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(pair[0]), rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_backward_q_block_skipped_on_every_kv_step(monkeypatch, dtype):
    """Traced offsets that leave q block 0 (rows 0..127) before every key
    (128..383): its tile is skipped on every kv step, so its ``dq`` is what
    the zeroing at the first kv block and the write-out at the last leave,
    both outside the ``_seen`` predicate. Block 1 crosses kv block 0 and
    skips kv block 1; the pair and the dense reference say the same."""
    rng = np.random.default_rng(28)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 256, 64)), dtype) for _ in range(3))
    fused, pair = _block_grads_both_ways(monkeypatch, q, k, v, True, q_start=0, k_start=128)
    assert not np.asarray(fused[0][:, :, :128]).any()
    assert np.asarray(fused[0][:, :, 128:]).any()
    tol = 1e-5 if dtype == jnp.float32 else _BF16_ULP
    for f, p in zip(fused, pair):
        np.testing.assert_allclose(np.asarray(f), np.asarray(p), rtol=tol, atol=tol)

    # dense: rows 128..255 see keys 128..(row), the rest see nothing
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    do = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape).astype(dtype).astype(jnp.float32)
    seen = jnp.arange(256)[:, None] >= (128 + jnp.arange(256))[None, :]

    def dense(q, k, v):
        s = jnp.where(seen, jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.125, -1e30)
        p = jnp.where(seen, jax.nn.softmax(s, -1), 0.0)
        return (jnp.einsum("bhqk,bhkd->bhqd", p, v) * do).sum()

    tol = 1e-4 if dtype == jnp.float32 else 4 * _BF16_ULP  # out and lse were rounded to bf16 first
    for f, e in zip(fused, jax.grad(dense, argnums=(0, 1, 2))(qf, kf, vf)):
        np.testing.assert_allclose(np.asarray(f), np.asarray(e), rtol=tol, atol=tol)


@pytest.mark.parametrize("s_q,d,block,itemsize,fused", [
    (8192, 64, 1024, 2, True),  # gpt2s-8k
    (1024, 64, 512, 2, True),  # gpt2s-1k, gpt2l-1k
    (8192, 128, 512, 2, True),  # jamba2-3b-8k
    (8192, 64, 1024, 4, True),
    (32768, 64, 1024, 2, True),
    (65536, 64, 1024, 2, False),  # 16 MiB of dqᵀ and 32 of double-buffered bf16 dq beside the tile: the pair
    (131072, 128, 512, 2, False),
])
def test_fused_backward_shape_rule(s_q, d, block, itemsize, fused):
    """The choice is a function of the shapes in hand against the VMEM budget."""
    from dsml_tpu.ops import flash

    need = flash._fused_bwd_vmem(s_q, d, block, block, itemsize)
    assert need >= s_q * d * 4  # never less than the resident float32 dq itself
    assert (need <= flash._VMEM_BUDGET) == fused


@pytest.mark.parametrize("causal", [True, False])
def test_fused_backward_through_the_vjp_matches_attention(causal):
    """The custom VJP end to end at 2x2 blocks with a cotangent on ``lse``
    too (``g_lse`` folds into ``dd`` before either path sees it)."""
    from dsml_tpu.ops.flash import flash_attention_lse

    q, k, v = _qkv(b=1, h=2, s=256, d=64, seed=28)
    w = jnp.cos(jnp.arange(64.0))

    def flash_loss(q, k, v):
        out, lse = flash_attention_lse(q, k, v, causal, block_q=128, block_k=128)
        return (out * w).sum() + (lse * jnp.sin(jnp.arange(256.0))).sum()

    def dense_loss(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.125
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, -1e30)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        return (out * w).sum() + (jax.scipy.special.logsumexp(s, -1) * jnp.sin(jnp.arange(256.0))).sum()

    assert _bwd_kernels(jax.grad(flash_loss, argnums=(0, 1, 2)), q, k, v) == ["flash_fwd", "flash_dkv"]
    for g, e in zip(jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v),
                    jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the projections' own layout: heads indexed out of [batch, seq, heads·head_dim]
# ---------------------------------------------------------------------------

# heads, head_dim, kv heads, seq, causal, q/k/v as one fused array, the backward as the pair
_PACKED = {
    "2-heads-of-64": (2, 64, 2, 256, True, True, False),
    "4-heads-of-64": (4, 64, 4, 256, True, True, False),  # two lane blocks
    "2-heads-of-128": (2, 128, 2, 256, True, True, False),  # one head a block: the head-major body exactly
    "grouped-4-on-2": (4, 64, 2, 256, True, False, False),  # kv heads repeated by the caller, three arrays
    "not-causal": (2, 64, 2, 256, False, True, False),
    "padded-203": (2, 64, 2, 203, True, True, False),  # the blocks do not divide it: split, padded, kv tail masked
    "pair-2-heads-of-64": (2, 64, 2, 256, True, False, True),  # flash_dq + flash_dkv, both two heads a block
}


def _packed_case(name):
    """(inputs, loss through the packed entry, through the head-major entry,
    through plain attention): each loss weighs ``out`` AND ``lse``."""
    from dsml_tpu.ops.flash import flash_attention_lse, flash_attention_packed

    heads, hd, kv, seq, causal, fused, _ = _PACKED[name]
    rng = np.random.default_rng(heads * hd + seq)
    widths = (heads * hd, kv * hd, kv * hd)
    inputs = [jnp.asarray(rng.standard_normal((2, seq, w)), jnp.float32) for w in widths]
    if fused:
        inputs = [jnp.concatenate(inputs, -1)]
    w_out = jnp.asarray(rng.standard_normal((2, seq, heads * hd)), jnp.float32)
    w_lse = jnp.asarray(rng.standard_normal((2, heads, seq)), jnp.float32)

    def split(inputs):  # q, k, v as [b, s, heads, hd], grouped k and v repeated
        q, k, v = jnp.split(inputs[0], 3, -1) if fused else inputs
        q, k, v = (t.reshape(2, seq, -1, hd) for t in (q, k, v))
        return q, jnp.repeat(k, heads // kv, 2), jnp.repeat(v, heads // kv, 2)

    def weigh(out, lse):
        return (out * w_out).sum() + (lse * w_lse).sum()

    def packed(*inputs):
        qkv = inputs[0] if fused else [t.reshape(2, seq, -1) for t in split(inputs)]
        return weigh(*flash_attention_packed(qkv, hd, causal, block_q=128, block_k=128))

    def head_major(*inputs):
        q, k, v = (t.transpose(0, 2, 1, 3) for t in split(inputs))
        out, lse = flash_attention_lse(q, k, v, causal, block_q=128, block_k=128)
        return weigh(out.transpose(0, 2, 1, 3).reshape(2, seq, -1), lse)

    def plain(*inputs):
        q, k, v = (t.transpose(0, 2, 1, 3) for t in split(inputs))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * hd**-0.5
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -1e30)
        out = attention(q, k, v, causal).transpose(0, 2, 1, 3).reshape(2, seq, -1)
        return weigh(out, jax.scipy.special.logsumexp(s, -1))

    return inputs, packed, head_major, plain


@pytest.mark.parametrize("against", ["attention", "head_major"])
@pytest.mark.parametrize("case", list(_PACKED))
def test_packed_entry_matches(monkeypatch, case, against):
    """``flash_attention_packed`` (q, k, v read out of the projections' own
    ``[b, s, heads·hd]``, ``128 // hd`` heads a grid step) against plain
    attention and against the head-major entry: ``out``, ``lse`` and the
    gradients of q, k, v under a cotangent on both."""
    from dsml_tpu.ops import flash

    if _PACKED[case][-1]:
        monkeypatch.setattr(flash, "_VMEM_BUDGET", 0)
    inputs, packed, head_major, plain = _packed_case(case)
    argnums = tuple(range(len(inputs)))
    wanted = ["flash_fwd", "flash_dq", "flash_dkv"] if _PACKED[case][-1] else ["flash_fwd", "flash_dkv"]
    assert _bwd_kernels(jax.grad(packed, argnums), *inputs) == wanted
    got = jax.jit(jax.value_and_grad(packed, argnums))(*inputs)
    other = plain if against == "attention" else head_major
    want = jax.jit(jax.value_and_grad(other, argnums))(*inputs)
    # the same operations in the same order as the head-major kernels; plain attention sums another way
    tol = 1e-4 if against == "attention" else 1e-5
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=tol)
    for g, e in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=tol, atol=tol)


def test_packed_entry_refuses_heads_that_do_not_fill_lane_blocks():
    from dsml_tpu.ops.flash import flash_attention_packed, flash_packs

    assert flash_packs(12, 64) and flash_packs(20, 64) and flash_packs(2, 64)
    assert not flash_packs(3, 64)  # 192 lanes: half a block left over
    assert not flash_packs(20, 128)  # one head a block: readable packed, measured slower (PERF.md §6)
    assert not flash_packs(8, 8) and not flash_packs(4, 32) and not flash_packs(4, 96) and not flash_packs(2, 256)
    with pytest.raises(ValueError, match="128-lane blocks"):
        flash_attention_packed(jnp.zeros((1, 128, 3 * 192)), 64)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_model_at_head_64_equals_the_head_major_route(monkeypatch, family):
    """A tiny model whose heads fill 128-lane blocks takes the packed entry
    (``_flash_packs``: chosen from shapes); its loss and every gradient equal
    what the head-major route gives, forced by denying the rule."""
    from dsml_tpu.models import gpt2, llama
    from dsml_tpu.ops import flash

    sizes = dict(vocab_size=512, max_seq=128, n_layer=2, n_head=4, d_model=256, d_ff=256)
    model = (gpt2.GPT2(gpt2.GPT2Config(**sizes)) if family == "gpt2"
             else llama.Llama(llama.LlamaConfig(n_kv_head=2, **sizes)))
    params = model.init(0)
    rng = np.random.default_rng(33)
    tokens, targets = (jnp.asarray(rng.integers(0, 512, size=(2, 128)), jnp.int32) for _ in range(2))

    def run():
        fn = jax.value_and_grad(lambda p: model.loss_spmd(p, tokens, targets, attn_impl="flash"))
        text = str(jax.make_jaxpr(fn)(params))
        return jax.jit(fn)(params), text.count("transpose[")

    (loss, grads), transposes = run()
    monkeypatch.setattr(flash, "flash_packs", lambda n_head, head_dim: False)
    (loss_major, grads_major), transposes_major = run()
    assert transposes < transposes_major  # the head-major copies are what the packed route leaves out
    np.testing.assert_allclose(float(loss), float(loss_major), rtol=1e-6)
    flat, flat_major = jax.tree.leaves(grads), jax.tree.leaves(grads_major)
    assert len(flat) == len(flat_major)
    for g, e in zip(flat, flat_major):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# the walk: one grid axis over the list of live tiles (PR 36)
# ---------------------------------------------------------------------------

# s_q, s_kv, block_q, block_k, causal, window, q_start − k_start (None: traced)
_WALKS = {
    "causal": (512, 512, 128, 128, True, None, 0),
    "not-causal": (512, 512, 128, 128, False, None, 0),
    "window-over-a-block": (512, 512, 128, 128, True, 200, 0),
    "window-of-a-block": (512, 512, 128, 128, True, 128, 0),
    "window-of-one-key": (512, 512, 128, 128, True, 1, 0),
    "unequal-blocks": (512, 512, 64, 256, True, 300, 0),
    "kv-blocks-no-query-sees": (256, 768, 128, 64, True, None, 0),
    "chunk-at-the-end": (256, 768, 128, 64, True, None, 512),
    "q-blocks-whose-windows-hold-no-key": (768, 256, 64, 128, True, 100, 0),
    "q-blocks-before-every-key": (512, 512, 128, 128, True, None, -256),
    "odd-offset": (512, 384, 128, 128, True, 96, 37),
    "padded-203": (256, 256, 64, 64, True, 70, 0),  # what 203 rows pad to: the tail is masked, never skipped
    "traced": (640, 384, 128, 128, True, 100, None),
}


def _tiles_with_a_visible_key(s_q, s_kv, block_q, block_k, causal, window, offset):
    """Brute force over positions: the tiles in which some query sees some key."""
    if offset is None or not causal:
        return np.ones((s_q // block_q, s_kv // block_k), bool)
    distance = (offset + np.arange(s_q))[:, None] - np.arange(s_kv)[None, :]
    visible = (distance >= 0) & ((distance < window) if window is not None else True)
    return visible.reshape(s_q // block_q, block_q, s_kv // block_k, block_k).any(axis=(1, 3))


@pytest.mark.parametrize("kv_major", [False, True], ids=["q-major", "kv-major"])
@pytest.mark.parametrize("case", list(_WALKS))
def test_walk_lists_the_tiles_seen_and_a_tile_of_every_block(case, kv_major):
    """The tables the index maps read, against a brute-force count of visible
    keys: every tile with one is there, once, in the rectangle's own order
    (q-major: each q block's kv blocks ascending; kv-major: each kv block's q
    blocks ascending); what else is there is the one tile of a block that has
    none; the flags mark the first and last tile of each major block and the
    first and last appearance of each minor one."""
    from dsml_tpu.ops import flash

    s_q, s_kv, block_q, block_k, causal, window, offset = _WALKS[case]
    want = _tiles_with_a_visible_key(*_WALKS[case])
    live = flash._live_tiles(s_q // block_q, s_kv // block_k, block_q, block_k, causal, window, offset)
    q_of, kv_of, flags = (np.asarray(t) for t in flash._walk(live, kv_major))
    assert all(t.dtype == np.int32 for t in (q_of, kv_of, flags))

    tiles = list(zip(q_of.tolist(), kv_of.tolist()))
    assert len(set(tiles)) == len(tiles)
    assert set(tiles) >= set(zip(*np.nonzero(want)))
    assert set(q_of) == set(range(s_q // block_q)) and set(kv_of) == set(range(s_kv // block_k))
    fillers = set(tiles) - set(zip(*np.nonzero(want)))  # a block with no key to see keeps one tile
    assert all(not want[qi].any() or not want[:, ki].any() for qi, ki in fillers)
    assert len(fillers) <= (~want.any(1)).sum() + (~want.any(0)).sum()
    assert tiles == sorted(tiles, key=(lambda t: t[::-1]) if kv_major else None)

    major, minor = (kv_of, q_of) if kv_major else (q_of, kv_of)
    steps = np.arange(len(tiles))
    for bit, of, pick in ((flash._ROW_FIRST, major, min), (flash._ROW_LAST, major, max),
                          (flash._SEEN_FIRST, minor, min), (flash._SEEN_LAST, minor, max)):
        assert sorted(steps[flags & bit != 0]) == sorted(pick(steps[of == block]) for block in set(of))
    if offset is None or not causal:
        assert len(tiles) == want.size  # the rectangle: the list no one could shorten


@pytest.mark.parametrize("shape,window,walked,rectangle", [
    ((8192, 128), 1024, 45, 256),  # mellum2-8k, a sliding layer
    ((8192, 128), None, 136, 256),  # mellum2-8k's full layer, jamba2-3b-8k
    ((8192, 64), None, 36, 64),  # gpt2s-8k
    ((1024, 64), None, 3, 4),  # gpt2s-1k, gpt2l-1k, gpt2l-1k-dp4
    ((203, 64), None, 1, 1),
    ((2001, 128), 512, 7, 16),  # padded to 2048: rows of 1 + 2 + 2 + 2
])
def test_grid_steps_at_the_cells_shapes(shape, window, walked, rectangle):
    from dsml_tpu.ops.flash import grid_steps

    seq, head_dim = shape
    assert grid_steps(seq, seq, head_dim, window=window) == (walked, rectangle)
    assert grid_steps(seq, seq, head_dim, window=window, offset=None) == (rectangle, rectangle)


def _grids(fn, *args):
    """``{kernel name: grid}`` of the Pallas calls in ``fn``'s jaxpr."""
    return dict(_pallas_calls(fn, *args))


# s_q, s_kv, head_dim, block, causal, window, q_start, k_start, dtype
_BIT_EQUAL = {
    "causal": (384, 384, 64, 128, True, None, 0, 0, jnp.float32),
    "causal-bf16": (384, 384, 64, 128, True, None, 0, 0, jnp.bfloat16),
    "window": (384, 384, 64, 128, True, 150, 0, 0, jnp.float32),
    "window-head-128-bf16": (512, 512, 128, 128, True, 128, 0, 0, jnp.bfloat16),
    "padded-203": (203, 203, 64, 64, True, None, 0, 0, jnp.float32),
    "padded-203-window": (203, 203, 32, 64, True, 70, 0, 0, jnp.float32),
    "unequal-lengths-offset-128": (256, 512, 64, 128, True, None, 256, 128, jnp.float32),
    "q-block-before-every-key": (256, 256, 64, 128, True, None, 0, 128, jnp.float32),
    "kv-blocks-no-query-sees": (256, 384, 64, 128, True, None, 0, 0, jnp.float32),
    "not-causal": (384, 384, 64, 128, False, None, 0, 0, jnp.float32),
}


@pytest.mark.parametrize("case", list(_BIT_EQUAL))
def test_walk_is_bit_equal_to_the_rectangle(case):
    """Offsets known as the call is traced (Python ints: the walk over the
    live tiles) against the same offsets handed as ``jnp.int32`` (the
    rectangle, ``_seen`` deciding inside the step): the same tiles through the
    same bodies in the same order, so ``out``, ``lse`` and all three gradients
    are equal to the bit, under a cotangent on both outputs."""
    from dsml_tpu.ops import flash

    s_q, s_kv, d, block, causal, window, q_start, k_start, dtype = _BIT_EQUAL[case]
    rng = np.random.default_rng(s_q + s_kv + d)
    q = jnp.asarray(rng.standard_normal((1, 2, s_q, d)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((1, 2, s_kv, d)), dtype) for _ in range(2))
    w_out, w_lse = jnp.cos(jnp.arange(float(d))), jnp.sin(jnp.arange(float(s_q)))

    def run(qs, ks):
        def fn(q, k, v, *offsets):
            out, lse = flash.flash_attention_lse(q, k, v, causal, *(offsets or (qs, ks)), block, block, window=window)
            return (out.astype(jnp.float32) * w_out).sum() + (lse * w_lse).sum(), (out, lse)

        offsets = () if isinstance(qs, int) else (qs, ks)
        grads = jax.grad(fn, argnums=(0, 1, 2), has_aux=True)
        return jax.jit(grads)(q, k, v, *offsets), _grids(grads, q, k, v, *offsets)

    (walk_grads, walk_out), walk_grid = run(q_start, k_start)
    (rect_grads, rect_out), rect_grid = run(jnp.int32(q_start), jnp.int32(k_start))
    for got, want in zip((*walk_out, *walk_grads), (*rect_out, *rect_grads)):
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))

    pq, pk = -(-s_q // block) * block, -(-s_kv // block) * block
    walked, rectangle = flash.grid_steps(s_q, s_kv, d, causal, window, q_start - k_start, block, block)
    assert rectangle == (pq // block) * (pk // block)
    assert walk_grid == {"flash_fwd": (2, 1, walked), "flash_dkv": (2, 1, walked)}
    assert rect_grid == {"flash_fwd": (2, 1, rectangle), "flash_dkv": (2, 1, rectangle)}
    assert (walked < rectangle) == (causal and rectangle > 1)


@pytest.mark.parametrize("case", ["2-heads-of-64", "2-heads-of-128", "grouped-4-on-2", "padded-203", "pair-2-heads-of-64"])
def test_packed_walk_is_bit_equal_to_the_rectangle(monkeypatch, case):
    """The packed entry always knows its offsets (0 and 0); with that
    knowledge denied it steps through the rectangle, and loss and gradients
    are the same bits. The last case holds the pair (``flash_dq`` walks
    q-major as the forward does) to the same."""
    from dsml_tpu.ops import flash

    if _PACKED[case][-1]:
        monkeypatch.setattr(flash, "_VMEM_BUDGET", 0)
    inputs, packed, _, _ = _packed_case(case)
    argnums = tuple(range(len(inputs)))
    walk = jax.jit(jax.value_and_grad(packed, argnums))(*inputs)
    walk_grid = _grids(jax.grad(packed, argnums), *inputs)
    monkeypatch.setattr(flash, "_static_offset", lambda q_start, k_start: None)
    rectangle = jax.jit(jax.value_and_grad(packed, argnums))(*inputs)
    rect_grid = _grids(jax.grad(packed, argnums), *inputs)
    for got, want in zip(jax.tree.leaves(walk), jax.tree.leaves(rectangle)):
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert set(walk_grid) == set(rect_grid) == {"flash_fwd", "flash_dkv"} | ({"flash_dq"} if _PACKED[case][-1] else set())
    for name in walk_grid:
        assert walk_grid[name][:2] == rect_grid[name][:2] and walk_grid[name][2] < rect_grid[name][2]


@pytest.mark.parametrize("window", [None, 100])
@pytest.mark.parametrize("pair", [False, True], ids=["fused", "pair"])
@pytest.mark.parametrize("q_start,k_start", [(0, 0), (0, 128), (256, 0)])
def test_block_grads_walk_is_bit_equal_to_the_rectangle(monkeypatch, pair, window, q_start, k_start):
    """``flash_block_grads`` (no custom VJP between the caller's offsets and
    the kernels): Python ints walk the live tiles, tracers the rectangle, in
    the one kernel and in the pair; ``dq``, ``dk``, ``dv`` equal to the bit."""
    from dsml_tpu.ops import flash

    if pair:
        monkeypatch.setattr(flash, "_VMEM_BUDGET", 0)
    rng = np.random.default_rng(36)
    q, k, v, do = (jnp.asarray(rng.standard_normal((1, 2, 384, 64)), jnp.float32) for _ in range(4))
    out, lse = jax.jit(lambda: flash.flash_attention_lse(q, k, v, True, q_start, k_start, 128, 128, window=window))()

    def grads(*offsets):
        return flash.flash_block_grads(q, k, v, out, lse, do, None, True, *(offsets or (q_start, k_start)), 128, 128,
                                       window=window)

    traced = (jnp.int32(q_start), jnp.int32(k_start))
    for got, want in zip(jax.jit(grads)(), jax.jit(grads)(*traced)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    walk_grid, rect_grid = _grids(grads), _grids(grads, *traced)
    assert list(walk_grid) == list(rect_grid) == (["flash_dq", "flash_dkv"] if pair else ["flash_dkv"])
    walked = flash.grid_steps(384, 384, 64, True, window, q_start - k_start, 128, 128)[0]
    assert all(grid == (2, 1, walked) for grid in walk_grid.values())
    assert all(grid == (2, 1, 9) for grid in rect_grid.values())
