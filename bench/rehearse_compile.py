"""Compile each cell's epoch for a described TPU v5e chip set, on a host
with no chip, before any chip time is spent.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py phi4-g4-s2k ...

For every named cell: the program the window dispatches
(``ProtocolEngine``'s epoch at the cell's exact shapes and shardings) is
lowered over ``chips`` described devices of a ``v5e:2x2`` topology and
compiled by the TPU compiler. Nothing runs. Prints the compiler's memory per
device, the number of Mosaic kernels (``tpu_custom_call``) and the
collectives with the bytes of their results.

On a CPU host the attention code would take its CPU branch, so this script
steers it onto the kernels the chip runs: ``REPRO_FLASH=1`` and the
kernels' interpret mode off. The persistent compile cache is off: entries
compiled for a described chip cannot be read back here.
"""
from __future__ import annotations

import collections
import os
import re
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]


def rehearse(name: str, topo) -> None:
    import jax
    import jax.numpy as jnp

    from benchlib import program, spec
    from repro.launch.mesh import use_mesh
    cell = spec.load_cell(name)
    prog = program.build(cell, topo.devices[:cell.chips])
    T, G = prog.pcfg.T, prog.pcfg.n_groups
    tr = cell.traffic
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(prog.make_state, key, key)
    rows = jax.ShapeDtypeStruct((T, G, tr["rows_per_group"], tr["seq"]),
                                jnp.int32, sharding=prog.replicated)
    zero = jax.ShapeDtypeStruct((), jnp.float32, sharding=prog.replicated)
    with use_mesh(prog.mesh):
        compiled = prog.engine._epoch.lower(
            state, {"tokens": rows, "labels": rows}, zero, zero).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    colls = collections.Counter()
    nbytes = collections.Counter()
    for m in re.finditer(r"= (\S+) (all-gather|all-reduce|reduce-scatter|"
                         r"collective-permute|all-to-all)(-start)?\(", text):
        typ, op = m.group(1), m.group(2)
        colls[op] += 1
        dims = re.search(r"\[([\d,]*)\]", typ)
        n = 1
        for d in (dims.group(1).split(",") if dims and dims.group(1) else []):
            n *= int(d)
        size = 4 if typ.startswith(("f32", "s32", "u32")) else 2
        nbytes[op] += n * size
    gib = 2.0 ** 30
    print(f"{name}: chips {cell.chips}; per device: peak "
          f"{mem.peak_memory_in_bytes / gib:.2f} GiB, arguments "
          f"{mem.argument_size_in_bytes / gib:.2f}, output "
          f"{mem.output_size_in_bytes / gib:.2f}, alias "
          f"{mem.alias_size_in_bytes / gib:.2f}, temporaries "
          f"{mem.temp_size_in_bytes / gib:.2f} GiB; "
          f"{text.count('tpu_custom_call')} tpu_custom_call; collectives "
          + (", ".join(f"{op} x{n} ({nbytes[op] / gib:.2f} GiB of results)"
                       for op, n in sorted(colls.items())) or "none"),
          flush=True)


def main(argv=None) -> int:
    names = sys.argv[1:] if argv is None else argv
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_FLASH"] = "1"
    import jax
    from jax.experimental import topologies

    from repro.kernels.flash_attention import ops
    jax.config.update("jax_enable_compilation_cache", False)
    ops._default_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        rehearse(name, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
