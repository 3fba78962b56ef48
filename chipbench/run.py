#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with the reference beside its limit.  The checks are also the last lines
of standard error.  Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits nonzero.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def find_devices(chips: int):
    """The first ``chips`` TPU chips, or None (with a message) when JAX
    finds no TPU, too few of them, or a chip with no published peaks."""
    import jax

    from chipbench.peaks import peaks

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: JAX finds no TPU (platform {devices[0].platform!r});"
              f" nothing was run", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"chipbench: the cell needs {chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return None
    try:
        peaks(devices[0].device_kind)
    except ValueError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return None
    return devices[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from chipbench import harness
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    devices = find_devices(cell.chips)
    if devices is None:
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), devices, T_START)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
