"""Checkpoint/resume compat front-end over ``dsml_tpu.checkpoint``.

The reference has NO checkpointing at all (SURVEY.md §5.4: weights live in
client RAM and as opaque device bytes; a crash loses the run). This module
keeps the original :class:`Checkpointer` API (save/restore/latest_step)
while the real machinery lives in the ``dsml_tpu.checkpoint`` package: a
dependency-free NATIVE backend (sharded binary pieces + JSON manifest,
atomic rename commits, async background writes — ``docs/CHECKPOINT.md``).

Backend selection: native by default. Orbax is OPTIONAL — used only when
explicitly requested (``backend="orbax"`` or ``DSML_CKPT_BACKEND=orbax``)
AND importable.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import numpy as np

from dsml_tpu.utils.logging import get_logger

log = get_logger("checkpoint")


def _pick_backend(backend: str | None) -> str:
    backend = backend or os.environ.get("DSML_CKPT_BACKEND", "") or "native"
    if backend not in ("native", "orbax"):
        raise ValueError(f"unknown checkpoint backend {backend!r} (native | orbax)")
    return backend


class Checkpointer:
    """Training-state persistence: (params, opt_state, epoch/step metadata)
    persist atomically, restore is sharding-aware (arrays come back with
    the template's mesh placement), and async saves never stall the step
    loop. Thin front-end: ``backend="native"`` (default) delegates to
    :class:`dsml_tpu.checkpoint.CheckpointManager`; ``backend="orbax"``
    keeps the original orbax wrapper for environments where it works."""

    def __init__(self, directory: str, max_to_keep: int = 3,
                 backend: str | None = None):
        self.backend = _pick_backend(backend)
        self.directory = os.path.abspath(directory)
        if self.backend == "orbax":
            self._impl = _OrbaxCheckpointer(self.directory, max_to_keep)
        else:
            from dsml_tpu.checkpoint import CheckpointManager

            self._impl = _NativeCheckpointer(CheckpointManager(
                self.directory, max_to_keep=max_to_keep))

    def save(self, step: int, params: Any, opt_state: Any = None,
             meta: dict | None = None, wait: bool = True) -> None:
        """Persist training state. ``wait=False`` makes the save ASYNC: the
        device arrays are snapshotted to host before return and written in
        a background thread while training continues — the step loop never
        stalls on disk (call :meth:`wait_until_finished` before shutdown,
        or let the next save's barrier absorb it)."""
        self._impl.save(step, params, opt_state, meta, wait)

    def wait_until_finished(self) -> None:
        """Block until any in-flight async save has committed."""
        self._impl.wait_until_finished()

    def latest_step(self) -> int | None:
        return self._impl.latest_step()

    def restore(self, step: int | None = None, template: Any = None,
                partial: bool = False) -> dict:
        """Restore state. With ``template`` (a pytree of like-shaped arrays,
        e.g. freshly-initialized sharded params), arrays are restored with
        the template's shardings/dtypes — including onto a DIFFERENT mesh
        layout than the save used. ``partial=True`` restores only the
        subtree named by the template (e.g. params without opt_state — the
        inference-load path)."""
        return self._impl.restore(step, template, partial)

    def close(self) -> None:
        self._impl.close()


class _NativeCheckpointer:
    """State-dict adapter: the old API's (params, opt_state, meta) triple
    maps onto one ``{"params": ..., "opt_state": ..., "meta": ...}`` tree."""

    def __init__(self, manager):
        self.manager = manager

    def save(self, step, params, opt_state=None, meta=None, wait=True):
        state = {"params": params}
        if opt_state is not None:
            state["opt_state"] = opt_state
        if meta:
            state["meta"] = dict(meta)
        self.manager.save(step, state, wait=wait)

    def wait_until_finished(self):
        self.manager.wait_until_finished()

    def latest_step(self):
        return self.manager.latest_step()

    def restore(self, step=None, template=None, partial=False):
        return self.manager.restore(step, template=template, partial=partial)

    def close(self):
        self.manager.close()


class _OrbaxCheckpointer:
    """The original orbax.checkpoint.CheckpointManager wrapper (explicit
    opt-in only; see module docstring)."""

    def __init__(self, directory: str, max_to_keep: int = 3):
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self.directory = directory
        os.makedirs(self.directory, exist_ok=True)
        self.manager = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(max_to_keep=max_to_keep, create=True),
        )

    def save(self, step, params, opt_state=None, meta=None, wait=True):
        state = {"params": params}
        if opt_state is not None:
            state["opt_state"] = opt_state
        if meta:
            state["meta"] = dict(meta)
        # PyTreeSave (not StandardSave): the manager binds ONE handler per
        # item name, and only the PyTree handler supports partial restore
        self.manager.save(step, args=self._ocp.args.PyTreeSave(state))
        if wait:
            self.manager.wait_until_finished()
            log.info("saved checkpoint step %d -> %s", step, self.directory)
        else:
            log.info("scheduled async checkpoint save step %d -> %s", step, self.directory)

    def wait_until_finished(self):
        self.manager.wait_until_finished()

    def latest_step(self):
        return self.manager.latest_step()

    def restore(self, step=None, template=None, partial=False):
        step = step if step is not None else self.manager.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        if template is not None:
            ref = jax.tree.map(self._ocp.utils.to_shape_dtype_struct, template)
            # restore_args carry the template's dtypes AND shardings — plain
            # PyTreeRestore(item=...) would return the dtypes/placements the
            # checkpoint was written with
            restore_args = self._ocp.checkpoint_utils.construct_restore_args(template)
            restored = self.manager.restore(
                step,
                args=self._ocp.args.PyTreeRestore(
                    item=ref, restore_args=restore_args, partial_restore=partial
                ),
            )

            # belt-and-braces: orbax can hand scalar/replicated leaves back
            # on a single device even when the template is mesh-placed —
            # re-place any leaf whose sharding drifted
            def place(t, r):
                if (
                    isinstance(t, jax.Array)
                    and isinstance(r, jax.Array)
                    and r.sharding != t.sharding
                ):
                    return jax.device_put(r, t.sharding)
                return r

            return jax.tree.map(place, template, restored)
        return self.manager.restore(step, args=self._ocp.args.PyTreeRestore())

    def close(self):
        self.manager.close()


def save_arrays(path: str, tree: Any) -> None:
    """Dependency-free fallback: flat .npz of a pytree (used by the wire
    client, which holds plain numpy weights)."""
    flat, treedef = jax.tree.flatten(tree)
    np.savez(path, treedef=str(treedef), **{f"a{i}": np.asarray(x) for i, x in enumerate(flat)})


def load_arrays(path: str, like: Any) -> Any:
    flat, treedef = jax.tree.flatten(like)
    data = np.load(path)
    return jax.tree.unflatten(treedef, [data[f"a{i}"] for i in range(len(flat))])
