"""``chip_smoke.py`` and the compile-cache helper, as far as a CPU can show:
the smoke refuses to run without a TPU, its rehearsal drives the real entry
points end to end at tiny size, and the cache lands where it was told to."""

import json
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args, cwd, **env_overrides):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **env_overrides)
    proc = subprocess.run(
        [sys.executable, SMOKE, *args], capture_output=True, text=True,
        timeout=300, cwd=cwd, env=env,
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc.returncode, lines, proc.stderr


def test_smoke_without_a_tpu_fails(tmp_path):
    rc, lines, err = _run_smoke(cwd=tmp_path)
    assert rc != 0, err
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert "no TPU" in last["reason"]
    assert last["device"]["platform"] == "cpu"
    assert len(lines) == 1  # no phase ran


def test_smoke_rehearsal_passes_and_caches_where_told(tmp_path):
    cache = tmp_path / "cache"
    rc, lines, err = _run_smoke(
        "--rehearse", cwd=tmp_path, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert rc == 0, err[-2000:]
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}

    setup, train = (json.loads(ln) for ln in lines[:2])
    assert setup["compile_cache"] == {"dir": str(cache), "from": "environment"}
    assert train["phase"] == "train" and train["failures"] == []
    assert len(train["flash"]["losses"]) == 4
    assert len(train["xla_reference"]["losses"]) == 3
    # the environment's directory is the one the run wrote (by jax's own
    # count: each step compiled once, nothing to hit in a new directory, the
    # flash step stored — the xla one may compile in under the second jax
    # asks of an entry), and the checkout's default was left alone
    for run in (train["flash"], train["xla_reference"]):
        assert (run["compile_cache"]["requests"], run["compile_cache"]["hits"]) == (1, 0)
    assert train["flash"]["compile_cache"]["writes"] == 1
    assert train["flash"]["compiled_memory_bytes"]["temp"] > 0
    assert any(cache.iterdir())
    assert not (tmp_path / ".jax_cache").exists()


def test_compile_cache_helper_leaves_env_choice_alone(monkeypatch, tmp_path):
    from dsml_tpu.utils.platform import configure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_helper_default_is_the_checkout(monkeypatch, tmp_path):
    from dsml_tpu.utils.platform import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = []
    for cwd in (tmp_path, REPO):
        monkeypatch.chdir(cwd)
        got.append(configure_compile_cache())
        assert jax.config.jax_compilation_cache_dir == got[-1]
    # (the value stays set; conftest keeps the cache itself off in this process)
    assert got == [os.path.join(REPO, ".jax_cache")] * 2
