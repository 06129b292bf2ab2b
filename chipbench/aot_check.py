"""Compile every program a cell needs, at its real size, for a described
v5e (no chip attached), from the cell's configuration and traffic files:

    JAX_PLATFORMS=cpu python3 chipbench/aot_check.py --workload <name> [--reference]

The programs are the family's (``aot_programs`` in ``families/<name>.py``).
Prints ``memory_analysis()`` per device for each. Run by hand before a chip
call; nothing runs, so it says nothing about results or times. The compile
cache is off around it (such a compile cannot be read back).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GIB = 2.0 ** 30


def report(name, compiled, t0):
    ma = compiled.memory_analysis()
    kernels = compiled.as_text().count("tpu_custom_call")
    print(f"{name}: arguments {ma.argument_size_in_bytes / GIB:.2f} GiB "
          f"(aliased {ma.alias_size_in_bytes / GIB:.2f}), outputs "
          f"{ma.output_size_in_bytes / GIB:.2f}, temporaries "
          f"{ma.temp_size_in_bytes / GIB:.2f}; {kernels} tpu_custom_call(s); "
          f"compiled in {time.perf_counter() - t0:.0f} s", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import spec
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cell = spec.load_cell(args.workload)
    for name, compile_ in cell.family.aot_programs(
            cell.model, cell.traffic, SingleDeviceSharding(topo.devices[0]),
            args.reference):
        t0 = time.perf_counter()
        report(f"{cell.name} {name}", compile_(), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
