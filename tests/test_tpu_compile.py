"""Ask the TPU's compiler, without the chip: AOT-compile the Pallas kernels
at GPT-2-small geometry for a *described* v5e (``jax.experimental.topologies``)
and keep the answers as tests.

Nothing runs here, so nothing is said about results or times — a passed
compile is not a chip run. What this guards is what interpret mode cannot
see: Mosaic layout rules (tile alignment, unsupported ops on packed types)
and block-shape rules. A kernel the compiler refuses is a strict ``xfail``
whose ``reason`` quotes the refusal, and the case checks that the compiler
still says exactly that: a kernel that now compiles, a changed message or any
other error fails the case, so the PR that repairs the kernel's layout flips
the mark in the same diff. Every case passes ``interpret=False`` itself: under
``JAX_PLATFORMS=cpu`` the kernels' own default is the interpreter, which
compiles to zero ``tpu_custom_call``. The persistent compile cache is off for
the whole test process (``conftest.py``): a compile for a described chip
cannot be read back without the chip.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2. Made inside a fixture, never while the module is
    imported: only the worker that runs this file loads the TPU's library."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology: skip, whatever it raised
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


def _compile(topo, fn, *shapes):
    """Lower ``fn`` on shape-only arguments placed on one described v5e
    device and compile with the real TPU compiler; returns the HLO text."""
    sharding = SingleDeviceSharding(topo.devices[0])
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), shapes
    )
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# GPT-2-small attention geometry: batch 8, 12 heads, 1024 ctx, head_dim 64
_QKV = _sds((8, 12, 1024, 64), jnp.bfloat16)


def test_flash_fwd_compiles(topo):
    from dsml_tpu.ops.flash import flash_attention

    text = _compile(
        topo, lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        _QKV, _QKV, _QKV,
    )
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def flash_grad_text(topo):
    from dsml_tpu.ops.flash import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    return _compile(topo, jax.grad(loss, argnums=(0, 1, 2)), _QKV, _QKV, _QKV)


def test_flash_bwd_compiles(flash_grad_text):
    # forward + the one backward kernel (dq rides the dkv tile)
    assert flash_grad_text.count("tpu_custom_call") == 2


def _kernel_calls(text):
    """(instruction name, op_name components) of every Mosaic call in ``text``."""
    calls = re.findall(r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
                       r'op_name="([^"]*)"', text)
    return [(name, re.split(r"[/();]", op_name)) for name, op_name in calls]


_FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def _flash_kernels_in(text):
    """Which flash kernel each Mosaic call of ``text`` is, in program order."""
    return [k for _, tokens in _kernel_calls(text) for k in _FLASH_KERNELS if k in tokens]


@pytest.fixture(scope="module")
def flash_pair_grad_text(topo):
    """The backward of ONE (batch, head) at a query length whose resident
    ``dq`` (131072 rows: 32 MiB of float32 and 64 of double-buffered bf16)
    the shape rule refuses: the pair, ``flash_dq`` then ``flash_dkv``."""
    from dsml_tpu.ops.flash import flash_block_grads

    def grads(q, k, v, out, lse, do):
        return flash_block_grads(q, k, v, out, lse, do, causal=True, interpret=False)

    q, kv = _sds((1, 1, 131072, 64), jnp.bfloat16), _sds((1, 1, 1024, 64), jnp.bfloat16)
    return _compile(topo, grads, q, kv, kv, q, _sds((1, 1, 131072), jnp.float32), q)


@pytest.mark.parametrize("kernel", _FLASH_KERNELS)
def test_flash_kernels_carry_their_names(flash_grad_text, flash_pair_grad_text, kernel):
    """``name=`` on each ``pl.pallas_call`` names the instruction and is a path
    component of its ``op_name``: what ``benchmarks/scope_reduce.py`` tells
    the kernels apart by. The gradient at the cells' lengths is two calls,
    ``flash_fwd`` and ``flash_dkv``; ``flash_dq`` is held to the program
    where the shape rule keeps the pair."""
    text = flash_pair_grad_text if kernel == "flash_dq" else flash_grad_text
    calls = _kernel_calls(text)
    assert calls
    assert all(sum(k in tokens for k in _FLASH_KERNELS) == 1 for _, tokens in calls), calls
    mine = [name for name, tokens in calls if kernel in tokens]
    # the instruction is transpose_jvp_flash_dkv__.1 here, flash_dkv.1 under the step's shard_map
    assert mine and all(kernel in name for name in mine), calls
    if kernel == "flash_dq":
        assert sorted(_flash_kernels_in(text)) == ["flash_dkv", "flash_dq"]


# what no 1k case above holds the compiler to: the 8k geometry of `gpt2s-8k`
# (1024x1024 blocks: bf16 operands, the transposed tile with the dq dot's
# relayout, the forward's row chunks, the VMEM plan at the widest tile beside
# 2 MB of resident dq), a head of 128 (Llama presets; the scale stays on the
# scores, 512x512 blocks) and Jamba's 8k at head 128 (4 MB resident)
@pytest.mark.parametrize("shape", [(4, 12, 8192, 64), (2, 8, 1024, 128), (1, 20, 8192, 128)],
                         ids=["8k-head64", "1k-head128", "8k-head128"])
@pytest.mark.parametrize("what", ["fwd", "grad"])
def test_flash_compiles_at_other_geometries(topo, shape, what):
    from dsml_tpu.ops.flash import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    qkv = _sds(shape, jnp.bfloat16)
    text = _compile(topo, fwd if what == "fwd" else jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    # the gradient is exactly two Mosaic calls, and no flash_dq
    assert _flash_kernels_in(text) == (["flash_fwd"] if what == "fwd" else ["flash_fwd", "flash_dkv"])


# the packed entry at the cells' four layers: q, k, v read out of the projections' own
# [batch, seq, heads·head_dim] by 128-lane blocks (two heads of 64 a grid step at 512x512
# and at 1024x1024 blocks; one head of 128 on a repeated key-value head, which the models'
# rule leaves head-major and scripts/attn_layer_check.py still times packed)
@pytest.mark.parametrize("batch,seq,heads,head_dim", [
    (32, 1024, 12, 64), (4, 8192, 12, 64), (4, 1024, 20, 64), (1, 8192, 20, 128),
], ids=["gpt2s-1k", "gpt2s-8k", "gpt2l-1k", "jamba2-3b-8k"])
def test_packed_flash_compiles_at_the_cells_geometries(topo, batch, seq, heads, head_dim):
    from dsml_tpu.ops.flash import flash_attention_packed

    def loss(*qkv):
        out, _ = flash_attention_packed(qkv[0] if len(qkv) == 1 else qkv, head_dim, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    d = heads * head_dim
    # GPT-2: the fused projection's one array; Jamba: three (k and v repeated to the query heads)
    qkv = [_sds((batch, seq, 3 * d), jnp.bfloat16)] if head_dim == 64 else [_sds((batch, seq, d), jnp.bfloat16)] * 3
    text = _compile(topo, jax.grad(loss, argnums=tuple(range(len(qkv)))), *qkv)
    calls = _kernel_calls(text)
    assert _flash_kernels_in(text) == ["flash_fwd", "flash_dkv"]
    assert all(kernel in name for (name, _), kernel in zip(calls, ["flash_fwd", "flash_dkv"])), calls
    # nothing head-major is made on either side of the kernels
    assert not re.findall(rf"\[{batch},{heads},{seq},{head_dim}\]|\[{batch * heads},{seq},{head_dim}\]", text)


def _head_layout_copies(text, heads, head_dim):
    """The ``copy`` / ``transpose`` instructions under ``attn`` in compiled
    ``text`` whose result is a 4-D array holding ``heads`` and ``head_dim``
    as dimensions of their own: the head-major relayouts round the kernels."""
    found = []
    for line in text.splitlines():
        hit = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)
        if hit and "attn" in line:
            dims = [int(n) for n in hit.group(1).split(",")]
            if len(dims) == 4 and heads in dims and head_dim in dims:
                found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("heads,head_dim,packs", [(12, 64, True), (24, 32, False), (6, 128, False)],
                         ids=["head64-packed", "head32-head-major", "head128-head-major"])
def test_gpt2_block_holds_head_layout_copies_only_off_the_packed_path(topo, monkeypatch, heads, head_dim, packs):
    """The engagement counter of the packed path, as a test: the compiled
    ``jax.grad`` of one GPT-2 attention block at ``[4, 1024, 768]`` holds no
    copy or transpose of a head-major 4-D array where the shape rule packs
    (12 heads of 64) and still holds the parent's eight where it does not
    (24 heads of 32, 6 of 128)."""
    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.ops import flash

    monkeypatch.setattr(flash, "_interpret_default", lambda: False)  # compile the kernels, not the interpreter
    assert flash.flash_packs(heads, head_dim) == packs
    model = GPT2(GPT2Config(vocab_size=512, max_seq=1024, n_layer=1, n_head=heads, d_model=768, d_ff=3072))
    layer = jax.tree.map(lambda leaf: _sds(leaf.shape, jnp.bfloat16),
                         jax.eval_shape(lambda: model.init(0))["layers"][0])

    def loss(layer, x):
        with jax.named_scope("attn"):
            return (x + model._attn_block(layer, x, heads, None, None, "flash")).astype(jnp.float32).sum()

    text = _compile(topo, jax.grad(loss, argnums=(0, 1)), layer, _sds((4, 1024, 768), jnp.bfloat16))
    assert _flash_kernels_in(text) == ["flash_fwd", "flash_dkv"]
    copies = _head_layout_copies(text, heads, head_dim)
    assert (not copies) if packs else len(copies) == 8, copies


# the selective-scan pair at Jamba2-3B's Mamba geometry and the cell's length: one row of
# 8192, 5120 channels (40 lane tiles), state 16 (two float32 sublane tiles), bf16 in and out
@pytest.fixture(scope="module")
def scan_grad_text(topo):
    from dsml_tpu.ops.selective_scan import selective_scan

    def loss(*operands):
        return selective_scan(*operands, interpret=False).astype(jnp.float32).sum()

    wide, narrow = _sds((1, 8192, 5120), jnp.bfloat16), _sds((1, 8192, 16), jnp.bfloat16)
    return _compile(topo, jax.grad(loss, argnums=tuple(range(6))), wide, wide,
                    _sds((5120, 16), jnp.float32), narrow, narrow, _sds((5120,), jnp.float32))


@pytest.mark.parametrize("kernel", ["ssm_scan_fwd", "ssm_scan_bwd"])
def test_selective_scan_kernels_compile_and_carry_their_names(scan_grad_text, kernel):
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]*)"', scan_grad_text)
    assert len(calls) == 2, calls
    assert sum(kernel in re.split(r"[/();]", op_name) for op_name in calls) == 1, calls


def test_selective_scan_holds_no_state_history_in_hbm(scan_grad_text):
    """No ``[S, E, N]`` array in either direction: nothing the compiled gradient
    holds has as many elements as one row's state history (8192 x 5120 x 16)."""
    sizes = [np.prod([int(n) for n in dims.split(",")])
             for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", scan_grad_text)]
    assert max(sizes) == 8192 * 5120, max(sizes)


def test_loss_head_plans_no_more_than_the_vocabulary_scan(topo):
    """The loss head alone, ``value_and_grad`` at the GPT-2-small cells' shape
    (32,768 tokens of 768 over 50,257, bf16): ONE loop, and no more temp than
    the vocabulary scan it replaced planned here (1,162,600,448 bytes: its
    ``[32768, 8192]`` float32 logits, 1.07 GB, and the residuals), so a later
    change of ``ops/xent.py``'s block rule cannot silently move a cell's peak."""
    from dsml_tpu.ops.xent import block_rows, chunked_softmax_xent

    n, d, v = 32768, 768, 50257
    assert block_rows(n, v, d) == (8, 4096)
    sharding = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding) for shape, dtype in
            (((n, d), jnp.bfloat16), ((v, d), jnp.bfloat16), ((n,), jnp.int32))]
    compiled = jax.jit(jax.value_and_grad(chunked_softmax_xent, argnums=(0, 1))).lower(*args).compile()
    assert compiled.as_text().count(" while(") == 1
    assert compiled.memory_analysis().temp_size_in_bytes <= 1_162_600_448


# paged decode at GPT-2-small serving geometry: 8 slots, 12 heads,
# head_dim 64, page 16, 1024 ctx (64 table entries a slot)
_SLOTS, _HEADS, _HD, _PAGE, _CTX = 8, 12, 64, 16, 1024
_N_PT = _CTX // _PAGE
_N_PAGES = _SLOTS * _N_PT + 1


def _pool_layer(mode):
    if mode is None:
        kv = _sds((_N_PAGES, _HEADS, _PAGE, _HD), jnp.bfloat16)
        return {"k": kv, "v": kv}
    width, dt = (_HD // 2, jnp.uint8) if mode == "int4" else (_HD, jnp.int8)
    kv = _sds((_N_PAGES, _HEADS, _PAGE, width), dt)
    sc = _sds((_N_PAGES, _HEADS, _PAGE, 1), jnp.float32)
    return {"k": kv, "v": kv, "k_s": sc, "v_s": sc}


def _paged_compile(topo, mode, pipeline):
    from dsml_tpu.ops.paged_attention import paged_attention

    return _compile(
        topo, lambda q, layer, table, pos: paged_attention(
            q, layer, table, pos, mode, interpret=False, pipeline=pipeline),
        _sds((_SLOTS, _HEADS, 1, _HD), jnp.bfloat16), _pool_layer(mode),
        _sds((_SLOTS, _N_PT), jnp.int32), _sds((_SLOTS, 1), jnp.int32),
    )


class _Refused(Exception):
    """The compiler refused the kernel with the message the case quotes."""


def _refused(*values, message, why):
    """A case the compiler refuses today, carrying the message it gives.
    Strict, and only ``_Refused`` counts: see ``_answer``."""
    return pytest.param(
        *values, message, id="-".join(map(str, values)),
        marks=pytest.mark.xfail(strict=True, raises=_Refused, reason=f"{message} ({why})"),
    )


def _answer(refusal, compile_fn):
    """The compiled text of a kernel the compiler takes. For one it refuses,
    hold it to the quoted message, then hand the refusal to the xfail mark."""
    if refusal is None:
        return compile_fn()
    with pytest.raises(Exception, match=re.escape(refusal)) as caught:
        compile_fn()
    raise _Refused(refusal) from caught.value


_SHRUI = "Mosaic failed to compile TPU kernel: failed to legalize operation 'arith.shrui'"
_SHRUI_WHY = "_fold_page's nibble unpack shifts an i8 vector, vector<8x128x4xi8>"
_SLICE = ("Mosaic failed to compile TPU kernel: Slice shape along dimension 3 "
          "must be aligned to tiling (128), but is {}")
_SLICE_WHY = ("the slot ring DMAs one [page, head_dim] page out of a pool whose "
              "lane dim pads to 128")
_BLOCK = (
    "The Pallas TPU lowering currently requires that the last two dimensions "
    "of your block shape are divisible by 8 and 128 respectively, or be equal "
    "to the respective dimensions of the overall array"
)
_BLOCK_WHY = "the scale operand's block (1, 128) on an array (6, 3072)"


@pytest.mark.parametrize("mode,refusal", [
    pytest.param(None, None, id="fp"), pytest.param("int8", None, id="int8"),
    _refused("int4", message=_SHRUI, why=_SHRUI_WHY),
])
def test_paged_decode_single_buffer_compiles(topo, mode, refusal):
    text = _answer(refusal, lambda: _paged_compile(topo, mode, pipeline=False))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode,refusal", [
    _refused(None, message=_SLICE.format(64), why=_SLICE_WHY),
    _refused("int8", message=_SLICE.format(64), why=_SLICE_WHY),
    _refused("int4", message=_SLICE.format(32), why=_SLICE_WHY),
])
def test_paged_decode_pipelined_compiles(topo, mode, refusal):
    text = _answer(refusal, lambda: _paged_compile(topo, mode, pipeline=True))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("scheme,refusal", [
    _refused("int8", message=_BLOCK, why=_BLOCK_WHY),
    _refused("int4", message=_BLOCK, why=_BLOCK_WHY),
])
def test_quantized_matmul_compiles(topo, scheme, refusal):
    """The dequant-fused decode matmul at m=8 (one token a slot), d=768,
    n=3072 — GPT-2-small's MLP up-projection."""
    from dsml_tpu.ops.quantization import quantize_weight_blocks, quantized_matmul

    qwt = jax.eval_shape(
        lambda w: quantize_weight_blocks(w, scheme, 128), _sds((768, 3072), jnp.float32)
    )
    text = _answer(refusal, lambda: _compile(
        topo, lambda x, q: quantized_matmul(x, q, interpret=False),
        _sds((8, 768), jnp.bfloat16), qwt,
    ))
    assert "tpu_custom_call" in text


def test_quantize_pallas_compiles(topo):
    """The stochastic-rounding int8 gradient quantizer (on-core PRNG) over
    one 4 MiB f32 bucket = 2048 blocks of 512."""
    from dsml_tpu.ops.quantization import _quantize_pallas

    text = _compile(topo, _quantize_pallas, _sds((2048, 512), jnp.float32), _sds((), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_stream_hop_compiles(topo):
    """The fused ring hop (flash + in-kernel remote KV copy) on a 4-device
    ring over the described chips: each rank's 256-token shard of a
    1024-token sequence."""
    from dsml_tpu.ops.flash import flash_stream_hop

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("cp",))

    def hop(q, k, v):
        rank = jax.lax.axis_index("cp")
        out, lse, k_next, v_next = flash_stream_hop(
            q, k, v, jnp.bool_(True), dst=(rank + 1) % 4, src=(rank - 1) % 4,
            causal=False, interpret=False,
        )
        return out, k_next, v_next

    spec = P(None, None, "cp", None)
    fn = jax.jit(jax.shard_map(
        hop, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 3, check_vma=False,
    ))
    arg = jax.ShapeDtypeStruct(
        (8, 12, 1024, 64), jnp.bfloat16, sharding=NamedSharding(mesh, spec)
    )
    assert "tpu_custom_call" in fn.lower(arg, arg, arg).compile().as_text()


# -- the window, and the grouped matmuls of the expert layer, at `mellum2-8k`'s geometry ------------------

@pytest.mark.parametrize("window", [None, 1024], ids=["full", "window"])
@pytest.mark.parametrize("what", ["fwd", "grad"])
def test_flash_compiles_at_head_128_with_and_without_a_window(topo, window, what):
    """[1, 32, 8192, 128], key-value heads already repeated: the sliding and the full layers' calls."""
    from dsml_tpu.ops.flash import flash_attention

    def out(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False, window=window)

    fn = out if what == "fwd" else jax.grad(lambda q, k, v: out(q, k, v).astype(jnp.float32).sum(), (0, 1, 2))
    qkv = _sds((1, 32, 8192, 128), jnp.bfloat16)
    found = set(_flash_kernels_in(_compile(topo, fn, qkv, qkv, qkv)))
    assert found == ({"flash_fwd"} if what == "fwd" else {"flash_fwd", "flash_dkv"})


@pytest.mark.parametrize("what", ["fwd", "grad", "grad_pair"])
def test_flash_compiles_at_a_query_key_width_apart_from_the_value_width(topo, monkeypatch, what):
    """[1, 32, 8192, 192] q and k beside [1, 32, 8192, 128] v, head-major:
    `kanana2-8k`'s latent attention; the output and dv 128 wide, dq and dk 192.
    ``grad_pair``: with no VMEM to give, as at a query too long for the fused
    backward, ``flash_dq`` beside ``flash_dkv``."""
    from dsml_tpu.ops import flash
    from dsml_tpu.ops.flash import flash_attention

    if what == "grad_pair":
        monkeypatch.setattr(flash, "_VMEM_BUDGET", 0)

    def out(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    fn = out if what == "fwd" else jax.grad(lambda q, k, v: out(q, k, v).astype(jnp.float32).sum(), (0, 1, 2))
    qk, v = _sds((1, 32, 8192, 192), jnp.bfloat16), _sds((1, 32, 8192, 128), jnp.bfloat16)
    text = _compile(topo, fn, qk, qk, v)
    assert set(_flash_kernels_in(text)) == {"fwd": {"flash_fwd"}, "grad": {"flash_fwd", "flash_dkv"},
                                            "grad_pair": {"flash_fwd", "flash_dq", "flash_dkv"}}[what]


@pytest.mark.parametrize("window", [None, 1024], ids=["full", "window"])
def test_flash_compiles_on_the_rectangle_where_offsets_are_traced(topo, window):
    """What the ring and cp callers lower (PR 36): offsets that are values of the program, so the walk's
    tables list the whole rectangle (256 tiles of 512 here) and ``_seen`` decides inside the step."""
    from dsml_tpu.ops.flash import flash_attention_lse

    def loss(q, k, v, q_start, k_start):
        out, lse = flash_attention_lse(q, k, v, True, q_start, k_start, interpret=False, window=window)
        return out.astype(jnp.float32).sum() + lse.sum()

    qkv, at = _sds((1, 4, 8192, 128), jnp.bfloat16), _sds((), jnp.int32)
    text = _compile(topo, jax.grad(loss, (0, 1, 2)), qkv, qkv, qkv, at, at)
    assert _flash_kernels_in(text) == ["flash_fwd", "flash_dkv"]


@pytest.mark.parametrize("tile", [128, 256, 512])
def test_grouped_matmuls_compile_and_carry_their_names(topo, tile):
    """The three kernels of an expert matmul and its backward at 65,536 pairs over 64 experts of
    2304 x 896 and 896 x 2304 (each expert padded to whole tiles), by the names the benchmark reads."""
    from dsml_tpu.ops.grouped_matmul import grouped_matmul, n_row_tiles

    tiles = n_row_tiles(65536, 64, tile)
    for k, n in ((2304, 896), (896, 2304)):
        def loss(x, w, group):
            return grouped_matmul(x, w, group, tile, interpret=False).astype(jnp.float32).sum()

        text = _compile(topo, jax.grad(loss, (0, 1)), _sds((tiles * tile, k), jnp.bfloat16),
                        _sds((64, k, n), jnp.bfloat16), _sds((tiles,), jnp.int32))
        calls = [name for _, tokens in _kernel_calls(text) for name in ("gmm_fwd", "gmm_dx", "gmm_dw") if name in tokens]
        # dx, and dw as two halves of the rows; the forward is no part of a gradient that keeps no output
        assert sorted(calls) == ["gmm_dw", "gmm_dw", "gmm_dx"], calls
