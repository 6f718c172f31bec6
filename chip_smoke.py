"""The quickest proof that the trainer still starts on the chip.

    python chip_smoke.py              # one TPU chip: phase `train`
    python chip_smoke.py --chips 4    # four-chip host: phase `dp4` only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse    # CPU rehearsal, tiny

One process; it imports jax once and starts no child. The default run drives
GPT-2-small (``GPT2Config.small()`` unchanged, bf16) through the entry points
a user calls — ``build_mesh`` → ``init_hybrid`` → ``make_hybrid_train_step``
— for four steps on one seeded 8×1024 batch with ``attn_impl="flash"``, and
again for three steps with ``attn_impl="xla"`` as the reference. ``--chips 4``
runs only the dp=4 step and the one-device step it is compared with.

It never selects the CPU and catches nothing: without a TPU it prints
``{"ok": false, ...}`` and exits 1; a phase that raises ends the run with a
traceback. ``--rehearse`` is for the sandbox: ``GPT2Config.tiny()``, and it
skips the checks only a TPU can answer (the platform, ``tpu_custom_call`` in
the compiled step, per-device ``memory_stats`` — the CPU reports none).

Each phase prints one JSON line of notes (compile seconds, step ms, losses,
what the persistent compile cache served, the compiled step's memory, peak
bytes) — notes for the next PR, not benchmark numbers. The last line is the
verdict the driver reads: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import importlib.metadata
import json
import math
import os
import sys
import time

import jax
import numpy as np
import optax

from dsml_tpu.models.gpt2 import GPT2, GPT2Config
from dsml_tpu.parallel.hybrid import init_hybrid, make_hybrid_train_step
from dsml_tpu.parallel.mesh import MeshSpec, build_mesh
from dsml_tpu.utils.platform import configure_compile_cache

BATCH = 8
LR = 3e-4
# flash-vs-xla and dp4-vs-one-device losses: same seeded weights and batch,
# bf16 params and activations, attention (or the gradient mean) summed in a
# different order. Absolute, on losses near ln(vocab) ~ 10.8.
LOSS_TOL = 2e-2

# what jax's persistent compile cache did, counted from its own events:
# `requests` compiles consulted it, `hits` were served from it, `writes` were
# stored in it (a compile under a second is not stored)
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_cache_counts: collections.Counter = collections.Counter()


def _count_cache_event(event: str, **_) -> None:
    if event in _CACHE_EVENTS:
        _cache_counts[_CACHE_EVENTS[event]] += 1


jax.monitoring.register_event_listener(_count_cache_event)


def _cache_since(before: collections.Counter) -> dict:
    return {name: _cache_counts[name] - before[name] for name in _CACHE_EVENTS.values()}


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _version(dist: str) -> str | None:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def _device_report() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def _run_steps(model, mesh, attn_impl: str, x, y, n_steps: int, seed: int):
    """init_hybrid + make_hybrid_train_step on ``mesh``; ``n_steps`` on the
    one batch. The first call is timed as compile; the rest as step wall time
    around ``block_until_ready``; the cache counts cover the step calls alone.
    Returns the printable notes and the live ``(step, params, opt_state)``
    for the checks that need them."""
    opt = optax.adamw(LR)
    params, opt_state = init_hybrid(model, opt, mesh, seed=seed)
    step = make_hybrid_train_step(model, opt, mesh, attn_impl=attn_impl)
    losses, wall_ms = [], []
    cache_before = _cache_counts.copy()
    for _ in range(n_steps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, x, y)
        jax.block_until_ready((params, opt_state, loss))
        wall_ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(loss))
    notes = {
        "attn_impl": attn_impl,
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1} or {"dp": 1},
        "losses": losses,
        "compile_s": round(wall_ms[0] / 1e3, 3),
        "step_ms": [round(ms, 3) for ms in wall_ms[1:]],
        "compile_cache": _cache_since(cache_before),
    }
    return notes, (step, params, opt_state)


def _check(failures: list, ok: bool, what: str) -> None:
    if not ok:
        failures.append(what)


def _check_losses_agree(failures: list, got: list, want: list, what: str) -> None:
    diffs = [abs(a - b) for a, b in zip(got, want)]
    _check(failures, max(diffs) <= LOSS_TOL,
           f"{what}: losses differ by {max(diffs):.4g} > {LOSS_TOL} ({got} vs {want})")


def phase_train(model, x, y, seed: int, rehearse: bool) -> list:
    """One chip: four flash steps, checked against three xla steps."""
    failures: list = []
    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    flash, (step, params, opt_state) = _run_steps(model, mesh, "flash", x, y, 4, seed)
    losses = flash["losses"]
    ln_vocab = math.log(model.config.vocab_size)
    _check(failures, all(math.isfinite(v) for v in losses), f"non-finite loss: {losses}")
    _check(failures, abs(losses[0] - ln_vocab) <= 1.0,
           f"step-1 loss {losses[0]:.4f} not within 1.0 of ln(vocab)={ln_vocab:.4f}")
    _check(failures, losses[3] < losses[0],
           f"step-4 loss {losses[3]:.4f} not below step-1 loss {losses[0]:.4f}")
    # the flash kernel must be the Mosaic one, not the interpreter: read the
    # compiled step (jit hands back the executable the first call built, in
    # ~0.4 s on the chip, without another compile). The same object gives the
    # step's memory as the compiler planned it: on this runtime memory_stats'
    # peak counts live buffers and leaves the program's temp out.
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, x, y).compile()
    flash["tpu_custom_calls"] = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    flash["compiled_memory_bytes"] = {
        k: getattr(mem, f"{k}_size_in_bytes") for k in ("argument", "output", "alias", "temp")}
    flash["read_compiled_s"] = round(time.perf_counter() - t0, 3)
    if not rehearse:
        _check(failures, flash["tpu_custom_calls"] > 0,
               "no tpu_custom_call in the compiled flash step")
    del params, opt_state, compiled  # free the flash run's device state before the reference

    xla, _ = _run_steps(model, mesh, "xla", x, y, 3, seed)
    _check_losses_agree(failures, losses[:3], xla["losses"], "flash vs xla")
    stats = jax.devices()[0].memory_stats() or {}
    _emit({"phase": "train", "flash": flash, "xla_reference": xla,
           "loss_tol": LOSS_TOL, "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
           "failures": failures})
    return failures


def phase_dp4(model, x, y, seed: int, rehearse: bool) -> list:
    """Four chips: three dp=4 steps (two rows a chip), checked against the
    same three steps on a one-device mesh in this process."""
    failures: list = []
    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs four devices, jax reports {len(devices)}")
    dp4, (_, params, _) = _run_steps(
        model, build_mesh(MeshSpec(dp=4), devices[:4]), "flash", x, y, 3, seed)
    _check(failures, all(math.isfinite(v) for v in dp4["losses"]),
           f"non-finite loss: {dp4['losses']}")
    # code that has only ever seen one chip may have put everything on the first
    on = {len(leaf.sharding.device_set) for leaf in jax.tree.leaves(params)}
    _check(failures, on == {4}, f"parameter leaves live on {sorted(on)} devices, want 4")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices[:4]]
    if not rehearse:
        _check(failures, all(in_use), f"a device holds no bytes: bytes_in_use={in_use}")
    del params

    one, _ = _run_steps(model, build_mesh(MeshSpec(dp=1), devices[:1]), "flash", x, y, 3, seed)
    _check_losses_agree(failures, dp4["losses"], one["losses"], "dp=4 vs one device")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices[:4]]
    _emit({"phase": "dp4", "dp4": dp4, "one_device_reference": one,
           "loss_tol": LOSS_TOL, "bytes_in_use_after_dp4": in_use,
           "peak_bytes_in_use": peaks, "failures": failures})
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the dp=4 path and its one-device reference")
    ap.add_argument("--seed", type=int, default=0, help="weights and batch")
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal: tiny config, no TPU-only checks")
    args = ap.parse_args(argv)

    cache_dir = configure_compile_cache()
    device = _device_report()
    if device["platform"] != "tpu" and not args.rehearse:
        _emit({"ok": False, "reason": f"no TPU: jax.devices()[0].platform is "
               f"{device['platform']!r}; this smoke runs on the chip only", "device": device})
        return 1
    _emit({
        "phase": "setup", "device": device, "rehearse": args.rehearse,
        "jax": jax.__version__, "jaxlib": _version("jaxlib"), "libtpu": _version("libtpu"),
        "platform_version": jax.devices()[0].client.platform_version,
        "compile_cache": {
            "dir": cache_dir,
            "from": "environment" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "checkout",
        },
    })

    config = GPT2Config.tiny() if args.rehearse else GPT2Config.small()
    model = GPT2(dataclasses.replace(config, dtype="bfloat16"))
    tokens = np.random.default_rng(args.seed).integers(
        0, config.vocab_size, (BATCH, config.max_seq + 1), dtype=np.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]

    phase = phase_dp4 if args.chips == 4 else phase_train
    failures = phase(model, x, y, args.seed, args.rehearse)
    if failures:
        _emit({"ok": False, "reason": "; ".join(failures), "device": device})
        return 1
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
