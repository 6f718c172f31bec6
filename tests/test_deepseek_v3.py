"""DeepSeek-V3 at a tiny size on the CPU (one dense and two expert layers, four
heads whose query and key (16 + 8) are wider than their value (16), a 32-wide
latent, 8 experts of which a token takes 2, two shared, float32): the program
against ``benchmarks/reference/deepseek_v3.py``, the interleaved rotation, the
sigmoid router with its selection bias, the shares of the experts, and the mesh
axes that run and that raise."""

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

from benchmarks.families import deepseek_v3 as family  # noqa: E402
from benchmarks.reference import deepseek_v3 as reference  # noqa: E402
from dsml_tpu.models import experts  # noqa: E402
from dsml_tpu.models.deepseek_v3 import DeepseekV3, DeepseekV3Config, rotary_table  # noqa: E402
from dsml_tpu.parallel.hybrid import hybrid_loss_fn, init_hybrid, make_hybrid_train_step  # noqa: E402
from dsml_tpu.parallel.mesh import MeshSpec, build_mesh  # noqa: E402

SEQ = 96


def _sizes(cfg: DeepseekV3Config, experts_held=None) -> reference.Sizes:
    return reference.Sizes(
        num_attention_heads=cfg.n_head, qk_nope_head_dim=cfg.qk_nope_dim, qk_rope_head_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim, kv_lora_rank=cfg.kv_lora_rank, num_experts_per_tok=cfg.expert_top_k,
        routed_scaling_factor=cfg.routed_scaling, layer_types=cfg.layer_types, rms_norm_eps=cfg.rms_eps,
        rope_theta=cfg.rope_theta, experts_held=experts_held)


def _batch(cfg, rows=2, seed=0):
    tokens = np.random.default_rng(seed).integers(0, cfg.vocab_size, (rows, SEQ + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


@pytest.fixture(scope="module")
def both_sides():
    """The program's loss and gradients (through the hybrid step's own loss
    closure on one device, whole-block remat on) and the reference's."""
    cfg = DeepseekV3Config.tiny(remat=True)
    assert cfg.qk_head_dim != cfg.v_head_dim and cfg.layer_types == ("dense", "sparse", "sparse")
    model, (x, y) = DeepseekV3(cfg), _batch(cfg)
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
    jax.eval_shape(lambda: DeepseekV3(DeepseekV3Config.tiny()).init(0)))[0]]


@pytest.mark.parametrize("leaf", [leaf for leaf in _LEAVES if not leaf.endswith("['bias']")])
def test_every_gradient_leaf_matches_the_reference(both_sides, leaf):
    (_, got), (_, want) = both_sides
    got, want = (dict(zip(_LEAVES, jax.tree.leaves(tree)))[leaf] for tree in (got, want))
    assert float(jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel())) <= 1e-4


@pytest.mark.parametrize("leaf", [leaf for leaf in _LEAVES if leaf.endswith("['bias']")])
def test_the_selection_bias_takes_no_gradient(both_sides, leaf):
    (_, got), (_, want) = both_sides
    for tree in (got, want):
        assert not np.any(np.asarray(dict(zip(_LEAVES, jax.tree.leaves(tree)))[leaf]))


def test_reference_layer_grads_are_its_loss_function_s(both_sides):
    """The block-by-block pull the cell uses at the published widths gives the
    gradients of the one traceable function: the dense layer whole, the last
    expert layer's named experts."""
    cfg = DeepseekV3Config.tiny()
    params, (x, y) = DeepseekV3(cfg).init(0), _batch(cfg)
    got = reference.layer_grads(params, x, y, (0, 2), s=_sizes(cfg), experts={2: (1, 4, 6)})
    assert "moe" not in got[0] and sorted(got[2]["moe"]["experts"]) == [1, 4, 6]
    want = {0: both_sides[1][1]["layers"][0],
            2: reference.watched_leaves(both_sides[1][1]["layers"][2], got[2]["moe"]["experts"])}
    for i in (0, 2):
        for g, w in zip(jax.tree.leaves(got[i]), jax.tree.leaves(want[i])):
            assert g.shape == w.shape
            np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()) + 1e-12)


def test_the_sigmoid_derivative_fault_shows_in_the_router_s_gradient_first(both_sides):
    """The reference's ``sigmoid_derivative`` control: the loss unmoved (the
    forward is the sigmoid's), the router's gradient far past its limit in
    ``kanana2-8k`` (0.3), every other leaf moved by less than 0.1, the limit
    that holds them there: at this size only the router's limit sees it."""
    cfg = DeepseekV3Config.tiny()
    params, (x, y) = DeepseekV3(cfg).init(0), _batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_fn(p, x, y, s=_sizes(cfg), variant="sigmoid_derivative")))(params)
    want_loss, want = both_sides[1]
    assert abs(float(loss) - float(want_loss)) <= 1e-6
    errors = {leaf: float(jnp.linalg.norm((g - w).ravel()) / (jnp.linalg.norm(w.ravel()) + 1e-30))
              for leaf, g, w in zip(_LEAVES, jax.tree.leaves(grads), jax.tree.leaves(want))}
    routers = [leaf for leaf in errors if leaf.endswith("['router']")]
    assert len(routers) == 2 and min(errors[leaf] for leaf in routers) > 0.6
    assert max(e for leaf, e in errors.items() if leaf not in routers) < 0.1


@pytest.mark.parametrize("mesh_spec", [dict(dp=2), dict(fsdp=2)], ids=str)
def test_trains_through_the_hybrid_step(mesh_spec):
    cfg = DeepseekV3Config.tiny(remat=True)
    model, n = DeepseekV3(cfg), math.prod(mesh_spec.values())
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
    (dict(cp=2), "flash", "is sharded over"), (dict(dp=1), "ring", "takes one head width")], ids=str)
def test_axes_and_impls_it_does_not_compute_raise(mesh_spec, attn_impl, match):
    model = DeepseekV3(DeepseekV3Config.tiny())
    mesh = build_mesh(MeshSpec(**mesh_spec), jax.devices()[:math.prod(mesh_spec.values())])
    optimizer = optax.adamw(1e-3)
    step = make_hybrid_train_step(model, optimizer, mesh, attn_impl=attn_impl)
    params, opt_state = init_hybrid(model, optimizer, mesh, seed=0)
    x, y = _batch(model.config, rows=2)
    with pytest.raises(NotImplementedError, match=match):
        step(params, opt_state, x, y)


@pytest.mark.parametrize("entry", ["init_cache", "generate", "decode_step_slots_paged"])
def test_serving_entry_points_raise(entry):
    with pytest.raises(NotImplementedError, match="Reach 4"):
        getattr(DeepseekV3(DeepseekV3Config.tiny()), entry)()


def test_pipeline_raises():
    with pytest.raises(NotImplementedError, match="pp"):
        DeepseekV3(DeepseekV3Config.tiny()).param_specs(pp=True)


# -- the rotation -----------------------------------------------------------------

@pytest.mark.parametrize("cfg", [DeepseekV3Config.tiny(), DeepseekV3Config()], ids=["tiny", "published"])
@pytest.mark.parametrize("position", [0, 1, 17, 8191])
def test_interleaved_rotation_matches_the_closed_form(cfg, position):
    """Pair ``(2i, 2i + 1)`` turned through ``pos · theta^(-2i / d)``, each
    pair alone: ``[a cos - b sin, a sin + b cos]``."""
    t = jax.random.normal(jax.random.key(position), (1, 1, 3, cfg.qk_rope_dim))
    got = np.asarray(DeepseekV3(cfg)._rotate(t, rotary_table(cfg, jnp.asarray([position], jnp.int32))))
    t = np.asarray(t, np.float64)
    for i in range(cfg.qk_rope_dim // 2):
        angle = position * np.float32(cfg.rope_theta ** (-2 * i / cfg.qk_rope_dim))
        a, b = t[..., 2 * i], t[..., 2 * i + 1]
        np.testing.assert_allclose(got[..., 2 * i], a * np.cos(angle) - b * np.sin(angle), atol=2e-3)
        np.testing.assert_allclose(got[..., 2 * i + 1], a * np.sin(angle) + b * np.cos(angle), atol=2e-3)


def test_the_program_s_rotation_is_the_reference_s():
    cfg = DeepseekV3Config.tiny()
    t = jax.random.normal(jax.random.key(3), (1, SEQ, 2, cfg.qk_rope_dim))
    got = DeepseekV3(cfg)._rotate(t, rotary_table(cfg, jnp.arange(SEQ, dtype=jnp.int32)))
    want = reference._rotate(t[0].transpose(1, 0, 2), _sizes(cfg), "float32").transpose(1, 0, 2)[None]
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- the router -------------------------------------------------------------------

def _layer(tokens=96, d=64, f=32, n_experts=8, seed=0, bias_std=0.05):
    keys = jax.random.split(jax.random.key(seed), 6)
    p = {"router": jax.random.normal(keys[0], (d, n_experts)) * 0.1,
         "bias": jax.random.normal(keys[5], (n_experts,)) * bias_std,
         "w_gate": jax.random.normal(keys[1], (n_experts, d, f)) * 0.1,
         "w_up": jax.random.normal(keys[2], (n_experts, d, f)) * 0.1,
         "w_down": jax.random.normal(keys[3], (n_experts, f, d)) * 0.1}
    return p, jax.random.normal(keys[4], (tokens, d))


def test_the_bias_moves_the_choice_and_not_the_weights():
    p, x = _layer()
    top_e, w = experts.route(x, p["router"], 2, p["bias"], 2.448)
    plain_e, _ = experts.route(x, p["router"], 2, jnp.zeros_like(p["bias"]), 2.448)
    assert bool(jnp.any(top_e != plain_e))  # some tokens choose otherwise
    scores = jax.nn.sigmoid(x @ p["router"])
    chosen = jnp.take_along_axis(scores, top_e, axis=1)
    np.testing.assert_allclose(w, 2.448 * chosen / chosen.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w.sum(1), 2.448, rtol=1e-6)
    np.testing.assert_array_equal(top_e, jax.lax.top_k(scores + p["bias"], 2)[1])


def test_router_ties_go_to_the_lower_index():
    p, x = _layer()
    top_e, w = experts.route(x, jnp.zeros_like(p["router"]), 2, jnp.zeros_like(p["bias"]), 2.448)
    np.testing.assert_array_equal(top_e, np.tile([0, 1], (x.shape[0], 1)))
    np.testing.assert_allclose(w, 1.224, rtol=1e-6)


def test_sigmoid_expert_layer_and_its_gradients_match_the_dense_form():
    p, x = _layer()
    s = _sizes(DeepseekV3Config.tiny())
    weight = jax.random.normal(jax.random.key(9), x.shape)

    def dense(p, x):
        with jax.default_matmul_precision("highest"):
            return reference.moe(p, x, s=s)

    got = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(experts.expert_layer(
        p, x, top_k=2, tile=16, routed_scaling=2.448) * weight), (0, 1)))(p, x)
    want = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(dense(p, x) * weight), (0, 1)))(p, x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()) + 1e-12)


def test_the_two_halves_and_the_shared_experts_add_up_to_the_uncut_layer():
    """Two chips holding experts 0-3 and 4-7, each told its share and each
    running the shared experts for its own tokens once: the two routed parts
    and one shared part are the uncut reference layer's output."""
    cfg = DeepseekV3Config.tiny()
    layer = DeepseekV3(cfg).init(0)["layers"][1]
    x = jax.random.normal(jax.random.key(1), (SEQ, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole = reference.moe(layer["moe"], x, s=_sizes(cfg)) + reference._mlp(layer["shared"], x)
        shared = reference._mlp(layer["shared"], x)
    total = shared
    for first in (0, 4):
        share = DeepseekV3(DeepseekV3Config.tiny(experts_held=(first, 4)))
        moe = {**layer["moe"], **{name: layer["moe"][name][first:first + 4] for name in ("w_gate", "w_up", "w_down")}}
        total = total + share._moe_block(moe, x, None)
    np.testing.assert_allclose(total, whole, atol=1e-5)


def test_a_share_of_the_experts_trains_and_matches_the_reference():
    cfg = DeepseekV3Config.tiny(experts_held=(4, 4))
    model, (x, y) = DeepseekV3(cfg), _batch(cfg)
    params = model.init(0)
    assert params["layers"][1]["moe"]["w_gate"].shape[0] == 4 and params["layers"][1]["moe"]["router"].shape[1] == 8
    got = jax.jit(lambda p: model.loss_spmd(p, x, y, attn_impl="flash"))(params)
    want = reference.loss_fn(params, x, y, s=_sizes(cfg, (4, 4)))
    assert abs(float(got) - float(want)) <= 1e-5


def test_expert_load_counts_the_first_expert_layer():
    """Layer 0 is dense, so the counter reads layer 1 unless told another."""
    cfg = DeepseekV3Config.tiny()
    model, (x, _) = DeepseekV3(cfg), _batch(cfg)
    params = model.init(0)
    load = jax.jit(model.expert_load)(params, x)
    assert load.shape == (8,) and int(load.sum()) == x.size * cfg.expert_top_k
    at_1 = jax.jit(model.expert_load, static_argnames="layer")(params, x, layer=1)
    np.testing.assert_array_equal(load, at_1)


# -- the benchmark family ------------------------------------------------------------

def test_the_family_counts_the_held_parameters():
    """The cut the configuration file states: 1,678,926,336 held parameters
    and a selection bias of 128 in each of the four expert layers."""
    import json

    config = json.loads((Path(__file__).resolve().parents[1] / "benchmarks/configs/kanana-2-30b-a3b.json").read_text())
    shape = family.shape(config)
    assert shape["experts_held"] == (0, 64) and shape["n_experts"] == 128
    assert family.parameter_count(shape) == 1_678_926_336 + 4 * 128
    model = family.program_model(config)
    leaves = jax.tree.leaves(jax.eval_shape(lambda: model.init(0)))
    assert sum(leaf.size for leaf in leaves) == family.parameter_count(shape)
