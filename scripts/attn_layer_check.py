"""The attention layer without its projections, alone on the chip, at the cells'
shapes: the flash kernels reading the projections' own layout beside the head-major
form they read before (PR 33), values and time.

    chiprun -- python3 scripts/attn_layer_check.py [--parent-tree DIR] [--forms layout|head_major|packed|window]

The unit is what lies between ``wqkv`` and ``wo``: ``[B, S, 3·d]`` in (q, k, v side by
side as the fused projection leaves them; for the grouped-query shape q ``[B, S, d]``
and one key-value head ``[B, S, head_dim]`` each, repeated to the query heads),
``[B, S, d]`` out, ``jax.value_and_grad`` of a weighted sum of the output. The
shapes are the cells' layers, bf16: ``[32, 1024, 12x64]`` (``gpt2s-1k``),
``[4, 8192, 12x64]`` (``gpt2s-8k``), ``[4, 1024, 20x64]`` (``gpt2l-1k``, per chip at
dp=4 too), ``[1, 8192, 20x128]`` on one key-value head (``jamba2-3b-8k``) and ``[1,
8192, 32x128]`` on four (``mellum2-8k``: its full layer, and ``mellum2-8k-window`` its
three sliding ones, 1024 keys a query). Each row carries ``grid_steps``, ``[walked,
rectangle]``: the grid steps this tree's kernels take for one (batch, lane block) and
the tiles of the rectangle the kernels stepped through before PR 36
(``ops/flash.py::grid_steps``).
``head_major`` below is the parent's form: the split and the transposes of
``models/gpt2.py::_qkv_heads``, the kernels on ``[batch, heads, seq, head_dim]``,
``_merge_heads``. Its kernels are this tree's head-major entry (the same bodies, one
head a block) or, with ``--parent-tree DIR``, ``DIR/dsml_tpu/ops/flash.py`` (a
``git archive`` of the parent commit, for the A/B of the kernels themselves). Both
forms get the same inputs in one process. Time is the device's, from a
``jax.profiler`` trace of ``--calls`` calls after two warm ones, reduced by
``benchmarks/trace_reduce``: the union of the device's op intervals for the layer,
and of those the Mosaic calls named ``flash_fwd`` / ``flash_dkv`` / ``flash_dq`` for
the kernels by themselves (the host clock where the trace holds no device plane, as in
a CPU rehearsal: ``--rehearse``). The last line is one JSON object; exit 1 where the
loss differs from the parent form's by more than float32 rounding or a gradient by
more than ``--tolerance`` of the parent's largest magnitude.

``--forms`` picks the pair that is compared. ``layout`` (the default) is the above.
``head_major``: the head-major form on the parent's kernels beside the same form on
this tree's, the A/B of a change to the kernels at every layer shape of the cells
(the window of PR 35: ``jamba2-3b-8k`` within 1%; the walk of PR 36), and ``packed`` the
same of the packed form, which the head-64 cells run. ``window``: this tree's head-major form with
every earlier key beside the same with ``--window`` keys, at ``mellum2-8k``'s ``[1,
8192, 32x128]`` on 4 key-value heads; the two compute different things, so only the
times are compared (values: ``tests/test_flash_window.py`` and the cell's own check).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

# batch, seq, query heads, head_dim, key-value heads, keys a query sees (None: every earlier one)
SHAPES = {"gpt2s-1k": (32, 1024, 12, 64, 12, None), "gpt2s-8k": (4, 8192, 12, 64, 12, None),
          "gpt2l-1k": (4, 1024, 20, 64, 20, None), "jamba2-3b-8k": (1, 8192, 20, 128, 1, None),
          "mellum2-8k": (1, 8192, 32, 128, 4, None), "mellum2-8k-window": (1, 8192, 32, 128, 4, 1024)}
TRACE_DIR = REPO / ".bench_trace" / "attn_layer"
KERNELS = ("flash_fwd", "flash_dkv", "flash_dq")


def layer_forms(flash, parent_flash, n_head: int, head_dim: int, n_kv: int, forms: str = "layout", window=None):
    """``(parent, change)`` of ``forms``: each maps the layer's inputs to ``[B, S, d]``.
    ``window`` is the shape's own and both sides see it, but under ``forms="window"``,
    where it is what the change alone is given."""
    import jax.numpy as jnp

    repeat = n_head // n_kv

    def split(inputs):  # -> q, k, v as [B, S, heads, head_dim], grouped k and v repeated
        q, k, v = jnp.split(inputs[0], 3, axis=-1) if len(inputs) == 1 else inputs
        q, k, v = (t.reshape(*t.shape[:2], -1, head_dim) for t in (q, k, v))
        return q, jnp.repeat(k, repeat, axis=2), jnp.repeat(v, repeat, axis=2)

    def head_major_on(kernels, **more):
        def head_major(*inputs):
            q, k, v = (t.transpose(0, 2, 1, 3) for t in split(inputs))
            out = kernels.flash_attention(q, k, v, causal=True, **more).transpose(0, 2, 1, 3)
            return out.reshape(*out.shape[:2], -1)

        return head_major

    def packed_on(kernels, **more):
        def packed(*inputs):
            if len(inputs) == 3:
                inputs = [tuple(t.reshape(*t.shape[:2], -1) for t in split(inputs))]
            return kernels.flash_attention_packed(inputs[0], head_dim, causal=True, **more)[0]

        return packed

    if forms == "window":
        return head_major_on(flash), head_major_on(flash, window=window)
    own = {} if window is None else {"window": window}  # a parent from before PR 35 has no such argument
    parent = packed_on if forms == "packed" else head_major_on
    return parent(parent_flash, **own), (head_major_on if forms == "head_major" else packed_on)(flash, **own)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES), choices=list(SHAPES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--tolerance", type=float, default=2**-7,
                    help="largest |change - parent| / max|parent| allowed of a gradient (bf16: 2^-8 a rounding)")
    ap.add_argument("--forms", default="layout", choices=("layout", "head_major", "packed", "window"))
    ap.add_argument("--window", type=int, default=1024, help="keys a query sees under --forms window")
    ap.add_argument("--parent-tree", help="a checkout of the parent commit: its ops/flash.py runs the head-major form")
    ap.add_argument("--rehearse", action="store_true", help="tiny shapes, for a run without the chip")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks import trace_reduce
    from dsml_tpu.ops import flash

    parent_flash = flash
    if args.parent_tree:
        spec = importlib.util.spec_from_file_location(
            "parent_flash", Path(args.parent_tree) / "dsml_tpu" / "ops" / "flash.py")
        parent_flash = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent_flash)

    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform}), flush=True)

    def per_call_ms(fn, operands, label):
        """(the layer, its flash kernels alone by name) in ms a call, and the clock they were read on."""
        for _ in range(2):
            jax.block_until_ready(fn(*operands))
        where = TRACE_DIR / label
        shutil.rmtree(where, ignore_errors=True)
        t0 = time.perf_counter()
        with jax.profiler.trace(str(where)):
            for _ in range(args.calls):
                jax.block_until_ready(fn(*operands))
        host_ms = (time.perf_counter() - t0) * 1e3 / args.calls
        ops = next(iter(trace_reduce.load(str(where))["devices"].values()), None)
        if not ops:
            return host_ms, None, "host_clock"

        def busy_ms(events):
            return trace_reduce.length(trace_reduce.union([[e[2], e[2] + e[3]] for e in events])) / 1e6 / args.calls

        kernels = {k: busy_ms([e for e in ops if e[1] == "custom-call:tpu_custom_call" and k in e[0]]) for k in KERNELS}
        return busy_ms(ops), {k: ms for k, ms in kernels.items() if ms}, "device_trace"

    out, ok = {}, True
    for name in args.shapes:
        batch, seq, n_head, head_dim, n_kv, own_window = SHAPES[name]
        if args.rehearse:
            batch, seq, n_head, head_dim, n_kv, own_window = 2, 256, 2, 64, 2 if n_kv > 1 else 1, own_window and 64
        d = n_head * head_dim
        ks = jax.random.split(jax.random.key(args.seed), 4)
        if n_kv == n_head:
            operands = (jax.random.normal(ks[0], (batch, seq, 3 * d)).astype(jnp.bfloat16),)
        else:
            operands = tuple(jax.random.normal(k, (batch, seq, w)).astype(jnp.bfloat16)
                             for k, w in zip(ks, (d, n_kv * head_dim, n_kv * head_dim)))
        weight = jax.random.normal(ks[3], (batch, seq, d))

        def layer(form):
            return jax.jit(jax.value_and_grad(
                lambda *inputs: jnp.sum(form(*inputs).astype(jnp.float32) * weight), argnums=tuple(range(len(operands)))))

        window = own_window
        if args.forms == "window":
            window = min(args.window, seq // 4) if args.rehearse else args.window
        parent, change = map(layer, layer_forms(flash, parent_flash, n_head, head_dim, n_kv, args.forms, window))
        (l_p, g_p), (l_c, g_c) = parent(*operands), change(*operands)
        loss_diff, diff = abs(float(l_c) - float(l_p)) / abs(float(l_p)), {}
        for key, a, b in zip(("dq", "dk", "dv") if len(operands) == 3 else ("dqkv",), g_c, g_p):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            diff[key] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        del g_p, g_c
        p_ms, p_kernels, clock = per_call_ms(parent, operands, f"{name}-parent")
        c_ms, c_kernels, _ = per_call_ms(change, operands, f"{name}-change")
        row = {"shape": [batch, seq, n_head, head_dim, n_kv], "forms": args.forms, "clock": clock,
               "grid_steps": flash.grid_steps(seq, seq, head_dim, window=window),
               "parent_ms": p_ms, "change_ms": c_ms, "ratio": c_ms / p_ms,
               "parent_kernels_ms": p_kernels, "change_kernels_ms": c_kernels,
               "kernels_ratio": sum(c_kernels.values()) / sum(p_kernels.values()) if p_kernels else None,
               "relative_difference": {"loss": loss_diff, **diff}}
        ok = ok and (args.forms == "window" or (loss_diff <= 1e-5 and max(diff.values()) <= args.tolerance))
        out[name] = row
        print(json.dumps({name: row}), flush=True)
    out["ok"] = ok
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
