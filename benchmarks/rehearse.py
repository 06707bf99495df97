"""Compile a cell's step at its real size for a TPU v5e that is described and
not attached, and print what the chip's compiler says: the bytes on each
device and the collectives it put in. Costs no chip time; a 24-layer step
takes up to a minute and a half. A script for the builder, not a test.

    JAX_PLATFORMS=cpu python benchmarks/rehearse.py --workload <cell>

Nothing runs, so this says nothing about results or times, and a compile that
passes is not a chip run.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--topology", default="v5e:2x2")
    args = parser.parse_args(argv)

    from benchmarks.run import Data, program_argv

    data = Data(ROOT / "BENCHMARK.json")
    cell = data.cell(args.workload)
    config, traffic = data.config(cell["config"]), data.json("traffic", cell["traffic"])
    adapter = data.module("adapters", config["adapter"])
    program_args, _ = program_argv(config, traffic, seed=0)

    import jax
    from jax.experimental import topologies

    from atomo_tpu.cli import build_parser

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name=args.topology)
    step, abstract = adapter.abstract_step(build_parser().parse_args(program_args), topo.devices)
    compiled = step.lower(*abstract).compile()
    memory = compiled.memory_analysis()
    print(f"{cell['name']}: compiled for {args.topology}, {cell['chips']} chip(s)")
    for name in ("argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
        print(f"  {name}: {getattr(memory, name) / 2**30:.3f} GiB")
    live = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    print(f"  per device, arguments + outputs - aliased + temporaries: {live / 2**30:.3f} GiB")
    text = compiled.as_text()
    for name in COLLECTIVES:
        print(f"  {name}: {len(re.findall(rf'= [^=]*\b{name}(-start)?\(', text))}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
