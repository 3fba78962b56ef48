"""The paper's core experiment: METG(50%) across systems and patterns.

Reproduces the Figure 9 methodology on the four JAX execution backends
(paper Table 4 analogues) x four dependence patterns, printing the METG
table and one efficiency-vs-granularity curve (Figure 3 analogue).

Run: PYTHONPATH=src python examples/metg_study.py [--fast]
"""
import argparse
import sys

sys.path.insert(0, ".")  # for benchmarks.common when run from repo root

from benchmarks.common import BenchContext, metg_for
from repro.backends import backend_names


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--artifacts", default=None,
                    help="directory for BENCH_<scenario>.json files")
    args = ap.parse_args()
    n_points = 5 if args.fast else 7
    ctx = BenchContext(artifacts_dir=args.artifacts)

    cases = [("stencil", {}, 1), ("nearest", {"radix": 5}, 1),
             ("spread", {"radix": 5}, 1), ("nearest", {"radix": 5}, 4)]

    print(f"{'backend':14s} {'pattern':12s} {'METG(50%) us':>12s} "
          f"{'peak GFLOP/s':>13s}")
    for be in backend_names():
        hi = 512 if (args.fast or be == "host-dynamic") else 4096
        for pat, kw, ng in cases:
            name = pat + ("_x4" if ng > 1 else "")
            res = metg_for(ctx, be, pat, name=f"metg_study.{be}.{name}",
                           num_graphs=ng, iterations_hi=hi,
                           n_points=n_points, **kw)
            metg = (res.metg or float("nan")) * 1e6
            print(f"{be:14s} {name:12s} {metg:12.2f} "
                  f"{res.peak_rate / 1e9:13.2f}")

    print("\nefficiency vs granularity (xla-scan, stencil) — Fig 3 analogue:")
    res = metg_for(ctx, "xla-scan", "stencil",
                   name="metg_study.curve", iterations_hi=4096, n_points=8)
    for p in sorted(res.points, key=lambda p: -p.granularity):
        bar = "#" * int(p.efficiency * 40)
        print(f"  {p.granularity * 1e6:10.2f} us  {p.efficiency * 100:5.1f}% {bar}")
    print(f"  METG(50%) = {(res.metg or 0) * 1e6:.2f} us")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
