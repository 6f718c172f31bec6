"""Mellum at a tiny size on the CPU (one period of four layers, four query
heads of 32 on two key-value heads with ``4 x 32 != d_model``, 8 experts of
which a token takes 2, a window shorter than the sequence, float32): the
program against ``benchmarks/reference/mellum.py``, the expert layer's rules
(no token dropped, work from shapes alone, the shares add up), the two rotary
tables, the grouped matmuls, and the mesh axes that run and that raise."""

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.reference import mellum as reference  # noqa: E402
from dsml_tpu.models import experts  # noqa: E402
from dsml_tpu.models.mellum import Mellum, MellumConfig, rotary_tables  # noqa: E402
from dsml_tpu.ops.grouped_matmul import grouped_matmul, n_row_tiles  # noqa: E402
from dsml_tpu.parallel.hybrid import hybrid_loss_fn, init_hybrid, make_hybrid_train_step  # noqa: E402
from dsml_tpu.parallel.mesh import MeshSpec, build_mesh  # noqa: E402
from scripts.expert_layer_check import kernel_calls  # noqa: E402

SEQ = 96


def _sizes(cfg: MellumConfig, experts_held=None) -> reference.Sizes:
    return reference.Sizes(
        num_attention_heads=cfg.n_head, num_key_value_heads=cfg.n_kv_head, head_dim=cfg.head_dim,
        num_experts_per_tok=cfg.expert_top_k, sliding_window=cfg.window, layer_types=cfg.layer_types,
        rms_norm_eps=cfg.rms_eps, rope_theta=cfg.rope_theta, yarn_factor=cfg.yarn_factor,
        yarn_original_max=cfg.yarn_original_max, yarn_beta_fast=cfg.yarn_beta_fast,
        yarn_beta_slow=cfg.yarn_beta_slow, yarn_attention_factor=cfg.yarn_attention_factor,
        experts_held=experts_held)


def _batch(cfg, rows=2, seed=0):
    tokens = np.random.default_rng(seed).integers(0, cfg.vocab_size, (rows, SEQ + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


@pytest.fixture(scope="module")
def both_sides():
    """The program's loss and gradients (through the hybrid step's own loss
    closure on one device, whole-block remat on) and the reference's."""
    cfg = MellumConfig.tiny(remat=True)
    assert cfg.n_head * cfg.head_dim != cfg.d_model and cfg.window < SEQ
    model, (x, y) = Mellum(cfg), _batch(cfg)
    params = model.init(0)
    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    loss_fn = jax.shard_map(hybrid_loss_fn(model, "flash"), mesh=mesh,
                            in_specs=(model.param_specs(), P(), P()), out_specs=P(), check_vma=False)
    got = jax.jit(jax.value_and_grad(loss_fn))(params, x, y)
    want = jax.jit(jax.value_and_grad(lambda p: reference.loss_fn(p, x, y, s=_sizes(cfg))))(params)
    return got, want


def test_loss_matches_the_reference(both_sides):
    (got, _), (want, _) = both_sides
    assert abs(float(got) - float(want)) <= 1e-5


_LEAVES = [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_flatten_with_path(
    jax.eval_shape(lambda: Mellum(MellumConfig.tiny()).init(0)))[0]]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_matches_the_reference(both_sides, leaf):
    (_, got), (_, want) = both_sides
    got, want = (dict(zip(_LEAVES, jax.tree.leaves(tree)))[leaf] for tree in (got, want))
    assert float(jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel())) <= 1e-4


def test_reference_layer_grads_are_its_loss_function_s(both_sides):
    """The block-by-block pull the cell uses at the published widths gives the
    gradients of the one traceable function, for the watched layers and experts."""
    cfg = MellumConfig.tiny()
    params, (x, y) = Mellum(cfg).init(0), _batch(cfg)
    got = reference.layer_grads(params, x, y, (0, 3), s=_sizes(cfg), experts={0: (0, 2, 5, 7), 3: (1, 6)})
    assert sorted(got[0]["moe"]["experts"]) == [0, 2, 5, 7] and sorted(got[3]["moe"]["experts"]) == [1, 6]
    for i in (0, 3):
        want = reference.watched_leaves(both_sides[1][1]["layers"][i], got[i]["moe"]["experts"])
        for g, w in zip(jax.tree.leaves(got[i]), jax.tree.leaves(want)):
            assert g.shape == w.shape
            np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()))


def test_reference_picks_the_busiest_experts():
    """``busiest=3``: each layer's three experts with the largest ``w_down``
    gradient, the same gradients as the whole layer's at those experts."""
    cfg = MellumConfig.tiny()
    params, (x, y) = Mellum(cfg).init(0), _batch(cfg, rows=1)
    got = reference.layer_grads(params, x, y, (0, 3), s=_sizes(cfg), busiest=3)
    whole = reference.layer_grads(params, x, y, (0, 3), s=_sizes(cfg))
    for i in (0, 3):
        w_down = whole[i]["moe"]["w_down"]
        norms = np.asarray(jnp.sum(w_down ** 2, axis=(1, 2)))
        assert sorted(got[i]["moe"]["experts"]) == sorted(np.argsort(norms)[-3:].tolist())
        for e, leaves in got[i]["moe"]["experts"].items():
            np.testing.assert_array_equal(leaves["w_down"], w_down[e])
        np.testing.assert_array_equal(got[i]["attn"]["wq"], whole[i]["attn"]["wq"])


@pytest.mark.parametrize("mesh_spec", [dict(dp=2), dict(fsdp=2), dict(dp=2, fsdp=2)], ids=str)
def test_trains_through_the_hybrid_step(mesh_spec):
    cfg = MellumConfig.tiny(remat=True)
    model, n = Mellum(cfg), math.prod(mesh_spec.values())
    mesh = build_mesh(MeshSpec(**mesh_spec), jax.devices()[:n])
    optimizer = optax.adamw(1e-3)
    step = make_hybrid_train_step(model, optimizer, mesh, attn_impl="flash")
    params, opt_state = init_hybrid(model, optimizer, mesh, seed=0)
    x, y = _batch(cfg, rows=4)
    want = float(reference.loss_fn(model.init(0), x, y, s=_sizes(cfg)))
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, x, y)
        losses.append(float(loss))
    assert abs(losses[0] - want) <= 1e-4 and losses[2] < losses[0]


@pytest.mark.parametrize("mesh_spec,attn_impl,match", [
    (dict(tp=2), "flash", "is sharded over"), (dict(sp=2), "flash", "is sharded over"),
    (dict(cp=2), "flash", "is sharded over"), (dict(dp=1), "ring", "no window")], ids=str)
def test_axes_and_impls_it_does_not_compute_raise(mesh_spec, attn_impl, match):
    model = Mellum(MellumConfig.tiny())
    mesh = build_mesh(MeshSpec(**mesh_spec), jax.devices()[:math.prod(mesh_spec.values())])
    optimizer = optax.adamw(1e-3)
    step = make_hybrid_train_step(model, optimizer, mesh, attn_impl=attn_impl)
    params, opt_state = init_hybrid(model, optimizer, mesh, seed=0)
    x, y = _batch(model.config, rows=2)
    with pytest.raises(NotImplementedError, match=match):
        step(params, opt_state, x, y)


@pytest.mark.parametrize("entry", ["init_cache", "generate", "decode_step_slots_paged"])
def test_serving_entry_points_raise(entry):
    with pytest.raises(NotImplementedError, match="Reach 3"):
        getattr(Mellum(MellumConfig.tiny()), entry)()


def test_pipeline_raises():
    with pytest.raises(NotImplementedError, match="pp"):
        Mellum(MellumConfig.tiny()).param_specs(pp=True)


# -- the rotary tables ----------------------------------------------------------

@pytest.mark.parametrize("cfg", [MellumConfig.tiny(), MellumConfig(n_layer=28)], ids=["tiny", "published"])
@pytest.mark.parametrize("position", [0, 1, 17, 8191])
def test_rotary_tables_match_the_closed_form(cfg, position):
    tables = rotary_tables(cfg, jnp.asarray([position], jnp.int32))
    half = cfg.head_dim // 2
    for m in (0, 1, half // 3, half // 2, half - 1):
        base = cfg.rope_theta ** (-2 * m / cfg.head_dim)
        cos, sin = tables["sliding_attention"]
        assert float(cos[0, m]) == pytest.approx(math.cos(position * np.float32(base)), abs=2e-3)

        def dim(r):
            return cfg.head_dim * math.log(cfg.yarn_original_max / (2 * math.pi * r)) / (2 * math.log(cfg.rope_theta))

        low, high = max(math.floor(dim(cfg.yarn_beta_fast)), 0), min(math.ceil(dim(cfg.yarn_beta_slow)), cfg.head_dim - 1)
        ramp = min(max((m - low) / (high - low), 0.0), 1.0)
        freq = np.float32((1 - ramp) * base + ramp * base / cfg.yarn_factor)
        cos, sin = tables["full_attention"]
        assert float(cos[0, m]) == pytest.approx(cfg.yarn_attention_factor * math.cos(position * freq), abs=3e-3)
        assert float(sin[0, m]) == pytest.approx(cfg.yarn_attention_factor * math.sin(position * freq), abs=3e-3)


def test_published_yarn_ramp_edges():
    """At the published sizes the ramp runs from frequency 18 to 35 of 64: below
    it a frequency is kept, above it divided by 16."""
    cfg = MellumConfig(n_layer=28)
    one = rotary_tables(cfg, jnp.asarray([1], jnp.int32))
    angle = {kind: np.arctan2(np.asarray(t[1][0], np.float64), np.asarray(t[0][0], np.float64)) for kind, t in one.items()}
    ratio = angle["full_attention"] / angle["sliding_attention"]
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-4)
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-3)
    assert np.all(np.diff(ratio[18:36]) < 0)


# -- the expert layer -----------------------------------------------------------

def _layer(tokens=96, d=64, f=32, n_experts=8, seed=0):
    keys = jax.random.split(jax.random.key(seed), 5)
    p = {"router": jax.random.normal(keys[0], (d, n_experts)) * 0.5,
         "w_gate": jax.random.normal(keys[1], (n_experts, d, f)) * 0.1,
         "w_up": jax.random.normal(keys[2], (n_experts, d, f)) * 0.1,
         "w_down": jax.random.normal(keys[3], (n_experts, f, d)) * 0.1}
    return p, jax.random.normal(keys[4], (tokens, d))


def _dense_layer(p, x, top_k, held=None):
    """Every expert for every token, weighted by the renormalised top-k."""
    s = _sizes(MellumConfig.tiny(), held)
    s = reference.Sizes(**{**s.__dict__, "num_experts_per_tok": top_k})
    with jax.default_matmul_precision("highest"):
        return reference.moe(p, x, s=s)


def _share(p, first, count):
    return {**p, **{name: p[name][first:first + count] for name in ("w_gate", "w_up", "w_down")}}


def _expert_layer(top_k, tile, checkpointed):
    """The layer as a test calls it, or as a block of ``models/mellum.py``
    recomputed in the backward does: all made again but what ``PLAN_NAMES`` tags."""
    def run(p, x):
        return experts.expert_layer(p, x, top_k=top_k, tile=tile)

    return jax.checkpoint(run, policy=jax.checkpoint_policies.save_only_these_names(*experts.PLAN_NAMES)) if checkpointed else run


@pytest.mark.parametrize("checkpointed", [False, True], ids=["plain", "checkpointed"])
@pytest.mark.parametrize("top_k,tile", [(2, 16), (3, 32), (8, 16)])
def test_expert_layer_and_its_gradients_match_the_dense_form(top_k, tile, checkpointed):
    p, x = _layer()
    weight = jax.random.normal(jax.random.key(9), x.shape)
    layer = _expert_layer(top_k, tile, checkpointed)
    got = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(layer(p, x) * weight), (0, 1)))(p, x)
    want = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(_dense_layer(p, x, top_k) * weight), (0, 1)))(p, x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()))


def test_checkpointed_layer_runs_the_down_projection_once():
    """The weight lies before ``w_down``, so nothing of that product is a
    residual: the recomputed forward holds the gate's two grouped matmuls
    alone (3 + 2 ``gmm_fwd``; 3 + 3 with the weight behind the product), and
    no gather of the backward reads a grouped matmul's output."""
    p, x = _layer()
    layer = _expert_layer(2, 16, checkpointed=True)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(layer(p, x) ** 2), (0, 1)))(p, x).jaxpr
    assert [kernel_calls(jaxpr, name) for name in ("gmm_fwd", "gmm_dx", "gmm_dw")] == [5, 3, 3]
    (backward,) = [eqn.params["jaxpr"] for eqn in jaxpr.eqns if eqn.primitive.name == "remat2"]
    assert kernel_calls(backward, "gmm_fwd") == 2

    def gathers_of_products(jaxpr):  # the gathers of `jaxpr` itself that read what its grouped matmuls give
        products = {v for eqn in jaxpr.eqns for v in eqn.outvars
                    if any(kernel_calls(inner, "gmm_fwd") for inner in jax.core.jaxprs_in_params(eqn.params))}
        return sum(eqn.primitive.name == "gather" and eqn.invars[0] in products for eqn in jaxpr.eqns)

    assert gathers_of_products(jaxpr) == 1  # the combine: each token's rows out of the down projection's
    assert gathers_of_products(backward) == 0 and sum(e.primitive.name == "gather" for e in backward.eqns) >= 3


@pytest.mark.parametrize("checkpointed", [False, True], ids=["plain", "checkpointed"])
def test_padding_rows_reach_the_weight_gradients_as_zeros(checkpointed):
    """Token ``t`` on experts ``t % 8`` and ``(t + 1) % 8``: 24 pairs an expert
    in two tiles of 16, so every expert ends in 8 padding rows, which hold
    token 0's ``x`` and, in the backward, token 0's ``dy`` (not zero): their
    weight is 0, so no expert's gradient sees them."""
    p, x = _layer()
    t = jnp.arange(x.shape[0])
    x = x.at[:, :8].set(4.0 * jax.nn.one_hot(t % 8, 8) + 2.0 * jax.nn.one_hot((t + 1) % 8, 8))
    p["router"] = jnp.zeros_like(p["router"]).at[:8].set(5.0 * jnp.eye(8))
    top_e, _ = experts.route(x, p["router"], 2)
    np.testing.assert_array_equal(top_e, jnp.stack([t % 8, (t + 1) % 8], axis=1))
    row_pair, _, tile_group = experts.plan(top_e, (0, 8), 16)
    padding = np.asarray(row_pair < 0).reshape(-1, 16).sum(axis=1)
    assert all(padding[np.asarray(tile_group) == e].sum() >= 8 for e in range(8))
    weight = jax.random.normal(jax.random.key(9), x.shape)
    assert float(jnp.abs(weight[0]).min()) > 0
    layer = _expert_layer(2, 16, checkpointed)
    got = jax.jit(jax.grad(lambda p, x: jnp.sum(layer(p, x) * weight), (0, 1)))(p, x)
    want = jax.jit(jax.grad(lambda p, x: jnp.sum(_dense_layer(p, x, 2) * weight), (0, 1)))(p, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("router", ["zeros", "one_hot"])
def test_no_token_is_dropped_with_every_token_on_the_same_experts(router):
    """All 96 tokens on experts (0, 1) (equal logits: ties go to the lower
    index) or on (3, 5): every pair has a row and the layer is the dense form."""
    p, x = _layer()
    x = jnp.abs(x)
    p["router"] = (jnp.zeros_like(p["router"]) if router == "zeros"
                   else jnp.zeros_like(p["router"]).at[:, 3].set(1.0).at[:, 5].set(0.5))
    top_e, _ = experts.route(x, p["router"], 2)
    assert set(np.unique(top_e)) == ({0, 1} if router == "zeros" else {3, 5})
    row_pair, dest, _ = experts.plan(top_e, (0, 8), 16)
    assert int((row_pair >= 0).sum()) == 2 * 96 == len(set(np.asarray(dest).ravel())) and int(dest.min()) >= 0
    np.testing.assert_allclose(experts.expert_layer(p, x, top_k=2, tile=16), _dense_layer(p, x, 2), atol=1e-5)


def test_the_tile_count_is_the_same_for_uniform_and_collapsed_routing():
    """The grouped matmuls' grid is ``tile_group``'s length: a function of the
    shapes, whatever the router does; every tile names an expert that is held."""
    p, x = _layer(tokens=128)
    counts = {}
    for name, router in (("uniform", p["router"]), ("collapsed", jnp.zeros_like(p["router"]))):
        top_e, _ = experts.route(x, router, 2)
        row_pair, dest, tile_group = experts.plan(top_e, (0, 8), 16)
        counts[name] = (tile_group.shape[0], row_pair.shape[0])
        assert int(tile_group.min()) == 0 and int(tile_group.max()) == 7 and bool(jnp.all(jnp.diff(tile_group) >= 0))
        assert set(np.unique(tile_group)) == set(range(8))  # an expert without a pair still owns a tile
    assert counts["uniform"] == counts["collapsed"] == (n_row_tiles(256, 8, 16), 16 * n_row_tiles(256, 8, 16))
    lowered = {name: jax.jit(lambda p, x: experts.expert_layer(p, x, top_k=2, tile=16)).lower(
        {**p, "router": router}, x).as_text() for name, router in (("a", p["router"]), ("b", jnp.zeros_like(p["router"])))}
    assert lowered["a"] == lowered["b"]  # one program: nothing in it is shaped by the routing


@pytest.mark.parametrize("count", [2, 4])
def test_the_shares_add_up_to_the_uncut_reference_layer(count):
    """Chips holding experts 0-1, 2-3, 4-5, 6-7 (or 0-3, 4-7) of 8, each told
    its share, sum to what the uncut reference gives for the whole layer; the
    reference told a share gives that share."""
    p, x = _layer()
    whole = _dense_layer(p, x, 2)
    total = 0.0
    for first in range(0, 8, count):
        part = experts.expert_layer(_share(p, first, count), x, top_k=2, tile=16, experts_held=(first, count))
        np.testing.assert_allclose(part, _dense_layer(_share(p, first, count), x, 2, (first, count)), atol=1e-5)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=1e-5)


def test_a_share_of_the_experts_trains_and_matches_the_reference():
    cfg = MellumConfig.tiny(experts_held=(2, 4))
    model, (x, y) = Mellum(cfg), _batch(MellumConfig.tiny())
    params = model.init(0)
    assert params["layers"][0]["moe"]["w_gate"].shape[0] == 4 and params["layers"][0]["moe"]["router"].shape[1] == 8
    got = jax.jit(lambda p: model.loss_spmd(p, x, y, attn_impl="flash"))(params)
    want = reference.loss_fn(params, x, y, s=_sizes(cfg, (2, 4)))
    assert abs(float(got) - float(want)) <= 1e-5


def test_expert_load_counts_every_pair():
    cfg = MellumConfig.tiny()
    model, (x, _) = Mellum(cfg), _batch(MellumConfig.tiny())
    for layer in (0, 2):
        load = jax.jit(model.expert_load, static_argnames="layer")(model.init(0), x, layer=layer)
        assert load.shape == (8,) and int(load.sum()) == x.size * cfg.expert_top_k


# -- the grouped matmuls --------------------------------------------------------

@pytest.mark.parametrize("k,n", [(256, 128), (128, 256)])
@pytest.mark.parametrize("tile_group", [(0, 0, 1, 2, 2, 2, 3, 3), (0, 1, 2, 3, 3, 3, 3, 3), (0, 1, 1, 1, 1, 1, 2, 3)],
                         ids=["mixed", "late_heavy", "one_heavy"])
def test_grouped_matmul_forward_and_both_backward_products(k, n, tile_group):
    tile, group = 16, jnp.asarray(tile_group, jnp.int32)
    x = jax.random.normal(jax.random.key(0), (8 * tile, k))
    w = jax.random.normal(jax.random.key(1), (4, k, n))

    def plain(x, w):
        return jnp.einsum("tmk,tkn->tmn", x.reshape(8, tile, k), w[group]).reshape(-1, n)

    got = jax.jit(jax.value_and_grad(lambda x, w: jnp.sum(grouped_matmul(x, w, group, tile) ** 2), (0, 1)))(x, w)
    want = jax.jit(jax.value_and_grad(lambda x, w: jnp.sum(plain(x, w) ** 2), (0, 1)))(x, w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, v in zip(got[1], want[1]):
        np.testing.assert_allclose(g, v, atol=1e-5 * float(jnp.abs(v).max()))


def test_grouped_matmul_refuses_what_does_not_fit():
    with pytest.raises(ValueError, match="do not fit"):
        grouped_matmul(jnp.zeros((64, 32)), jnp.zeros((2, 16, 8)), jnp.zeros(4, jnp.int32), 16)
