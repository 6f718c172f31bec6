"""The optimised HLO of one benchmark cell's train step, compiled at its real
size for a *described* v5e (nothing runs, no chip), with ``metadata={...}``
stripped: what a change that touches only names (``jax.named_scope``, a
docstring, a moved line) must leave identical.

    JAX_PLATFORMS=cpu JAX_TRACEBACK_IN_LOCATIONS_LIMIT=0 \\
        python3 scripts/step_hlo.py <tree> <cell> [<out.txt>]

``<tree>`` is a checkout (``.`` or a ``git archive`` of another commit under
``.scratch/``); its own ``dsml_tpu`` and ``benchmarks`` are imported. Prints the
sha256 of the stripped text and how many op names hold each of the program's
inner names; run it on two trees and compare. Without
``JAX_TRACEBACK_IN_LOCATIONS_LIMIT=0`` the Mosaic kernels' payloads carry the
Python line numbers of their call sites and differ for a moved line.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp


def main(tree: str, cell: str, out: str | None = None) -> int:
    root = os.path.abspath(tree)
    os.chdir(root)
    sys.path.insert(0, root)

    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import dsml_tpu.ops.flash as flash
    from benchmarks import harness
    from benchmarks.drivers import train

    if not os.path.abspath(harness.__file__).startswith(root + os.sep):
        raise SystemExit(f"imported {harness.__file__}, not {root}'s benchmarks")
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's compile cannot be read back
    flash._interpret_default = lambda: False  # as benchmarks/size_cell.py: the kernels, not the interpreter

    _, _, config, traffic = harness.resolve(cell)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    _, model, mesh, optimizer, step = train.build_step(config, traffic, topo.devices[:traffic["chips"]])
    replicated = NamedSharding(mesh, P())

    def on_mesh(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=replicated), tree)

    params = jax.eval_shape(lambda: model.init(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    batch = jax.ShapeDtypeStruct((traffic["rows_per_chip"] * traffic["chips"], traffic["seq"]), "int32")
    text = step.lower(on_mesh(params), on_mesh(opt_state), batch, batch).compile().as_text()
    stripped = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    if out:
        with open(out, "w") as f:
            f.write(stripped)
    op_names = [re.split(r"[/();]", name) for name in set(re.findall(r'op_name="([^"]*)"', text))]
    print(json.dumps({
        "tree": root, "cell": cell, "hlo_bytes": len(text), "stripped_bytes": len(stripped),
        "metadata_fields": text.count("metadata={"), "sha256_stripped": hashlib.sha256(stripped.encode()).hexdigest(),
        "op_names_holding": {name: sum(name in tokens for tokens in op_names)
                             for name in ("normalize", "rope", "kv_repeat", "ssm_conv", "rematted_computation")},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))
