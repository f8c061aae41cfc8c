"""CPU rehearsal of a cell's control flow at a tiny size.

    JAX_PLATFORMS=cpu python -m benchmark.rehearse --workload <cell> [--seconds 3] [--trace 0|1] [--devices 4]

Same files, same driver, same harness as ``benchmark.run``; the sizes come from
the ``rehearsal`` entry of the cell's configuration, traffic and workload files. It never prints a result line (the last line says so), so a
number from here cannot be taken for a device metric. ``--devices 4`` gives the
CPU backend four virtual devices for the four-chip cell's mesh.
"""

from __future__ import annotations

import argparse
import os
import sys


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--devices", type=int, default=1)
    args = parser.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    if args.devices > 1:
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={args.devices}"
    import jax

    # no persistent cache: CPU programs have no business in the checkout's cache
    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark import run

    run.execute(args.workload, args.seed, args.seconds, bool(args.trace), rehearsal=True)
    print("rehearsal finished: no result line")
    return 0


if __name__ == "__main__":
    sys.exit(main())
