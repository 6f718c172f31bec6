"""Size a cell without the chip: compile its step, at its real size, for a
*described* v5e:2x2 with the TPU's own compiler, and print what the compiler
plans to hold on each device. Nothing runs; a compile that passes is not a chip
run and gives no time.

    JAX_PLATFORMS=cpu python3 benchmarks/size_cell.py --workload gpt2l-1k [--rows-per-chip 6]

The rule the first four cells were sized by: the most rows a chip (a multiple
of 8 for GPT-2-small at 1k, of 2 for GPT-2-large, whole sequences at 8k) whose
arguments + temp stay at or under 13.5 GB of the chip's 16.

Under ``JAX_PLATFORMS=cpu`` the flash kernels' own default is the Pallas
interpreter, which compiles to zero ``tpu_custom_call``; this script turns that
default off for itself (``ops.flash._interpret_default``), as
``tests/test_tpu_compile.py`` passes ``interpret=False``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows-per-chip", type=int, help="try another count than the traffic file's")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import dsml_tpu.ops.flash as flash
    from benchmarks import harness
    from benchmarks.drivers import train

    # a compile for a described chip cannot be read back from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    flash._interpret_default = lambda: False

    _, cell, config, traffic = harness.resolve(args.workload)
    rows_per_chip = args.rows_per_chip or traffic["rows_per_chip"]
    rows, seq = rows_per_chip * traffic["chips"], traffic["seq"]

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    _, model, mesh, optimizer, step = train.build_step(
        config, traffic, topo.devices[:traffic["chips"]])
    replicated = NamedSharding(mesh, P())

    def on_mesh(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=replicated), tree)

    params = jax.eval_shape(lambda: model.init(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    batch = jax.ShapeDtypeStruct((rows, seq), "int32")
    t0 = time.perf_counter()
    lowered = step.lower(on_mesh(params), on_mesh(opt_state), batch, batch)
    lower_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    arg, temp = mem.argument_size_in_bytes, mem.temp_size_in_bytes
    print(json.dumps({
        "cell": cell["name"], "rows_per_chip": rows_per_chip, "seq": seq, "mesh": traffic["mesh"],
        "argument_bytes": arg, "temp_bytes": temp, "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes, "argument_plus_temp_gb": (arg + temp) / 1e9,
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count(" all-reduce("), "all_reduce_starts": text.count(" all-reduce-start("),
        "lower_s": lower_s, "compile_s": compile_s,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
