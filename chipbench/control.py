#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 chipbench/control.py --workload <cell> --seeds 12 --seconds 6

In one process, with one set-up: for each seed a window of ``--seconds``
at the cell's own sizes, every output compared with the float32
reference (the program's readings); then the control, the reference
computed in bfloat16 (payloads stored in bfloat16, the kernel computed
in it), put in the program's place for the same runs and compared the
same way.  For the first ``--fault-seeds`` seeds, one more window with
the coarsest point cut short (``coarse_cut``).  Prints one JSON line per
reading and exits 0; without a TPU it prints nothing and exits nonzero.
The benchmark's own runs do not run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_outputs(cell, points):
    """The bfloat16 reference in the program's place: one output for each
    run each point made."""
    from chipbench import reference

    c = cell.config
    payload = max(5, c["payload_bytes"] // 4)
    for p in points:
        low = reference.final_payload(cell.spec["width"], c["height"],
                                      c["dep_offsets"], p.iterations, payload,
                                      dtype=reference.BFLOAT16)
        p.outputs = [low] * len(p.outputs)


def coarse_cut(points):
    """The fault that ``iteration_time_ratio`` is for, planted in the
    program: the coarsest point runs the next coarser point's program, so
    its runs stop at that point's iteration count.  Returns the undo."""
    nxt, top = sorted(points, key=lambda p: p.iterations)[-2:]
    runner = top.runner
    top.runner = nxt.runner

    def undo():
        top.runner = runner
    return undo


def readings(cell, points, backend, seeds, seconds, fault_seeds=0, log=print):
    from chipbench import harness

    def measure(side, seed):
        for p in points:
            p.outputs, p.times, p.runs, p.seconds = [], [], 0, 0.0
            p.ahead_runs, p.ahead_seconds = 0, 0.0
        harness.window(points, seconds, seed)
        note(side, seed)

    def note(side, seed):
        runs, failed, checks = harness.check(cell, points, backend)
        log(json.dumps({"cell": cell.name, "side": side, "seed": seed,
                        "runs": runs, "failed": failed,
                        "correct": harness.passed(checks), "checks": checks}))

    for i, seed in enumerate(seeds):
        measure("program", seed)
        control_outputs(cell, points)
        note("control_bf16", seed)
        if i < fault_seeds:
            undo = coarse_cut(points)
            measure("fault_coarse_cut", seed)
            undo()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_000_000_001)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from chipbench import harness
    from chipbench.run import find_devices
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    devices = find_devices(cell.chips)
    if devices is None:
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with jax.default_device(devices[0]):
        backend, points = harness.build(cell, devices)
        print(f"set-up {time.perf_counter() - T_START:.3f} s", file=sys.stderr)
        seeds = [args.first_seed + 7919 * k for k in range(args.seeds)]
        readings(cell, points, backend, seeds, args.seconds, args.fault_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
