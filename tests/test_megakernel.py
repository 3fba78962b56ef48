"""The fused megakernel backend: structure, tables, model, baselines.

Bit-exact conformance of ``pallas-fused`` rides the shared matrices in
test_conformance.py / test_backends.py (it registers like any backend).
This file pins what is *specific* to the tentpole:

* the fusion claim itself — the TPU lowering of the fused program is a
  single kernel launch with no dispatch loop, while ``xla-scan``'s is a
  ``while`` loop with no kernel launch (structural, not clock-based);
* the dense dependency-table form the kernel consumes;
* the per-launch synthetic dispatch model and the committed baselines
  showing the METG undercut.
"""
import json
import os

import numpy as np
import pytest

from repro.backends import backend_names, get_backend
from repro.core import (check_outputs, execute_reference, make_graph,
                        pattern_names, replicate)

BASELINES = os.path.join(os.path.dirname(__file__), "..",
                         "benchmarks", "baselines")


def small_graph(**kw):
    kw.setdefault("width", 8)
    kw.setdefault("height", 6)
    kw.setdefault("pattern", "stencil")
    kw.setdefault("iterations", 4)
    return make_graph(**kw)


# ------------------------------------------------------------ registration
def test_registered_with_fused_dispatch_model():
    assert "pallas-fused" in backend_names()
    be = get_backend("pallas-fused")
    assert be.dispatch_model == "per-launch"
    # CPU hosts auto-select interpret mode; the option is spellable too
    assert be.interpret is True
    assert get_backend("pallas-fused[interpret=True]").interpret is True


# ------------------------------------------------------- the fusion claim
def test_fused_program_is_a_single_kernel_launch():
    """The tentpole, pinned structurally: all H timesteps of the graph
    lower into exactly one Pallas launch (`tpu_custom_call`) and no
    dispatch loop, while xla-scan's program is a `stablehlo.while` that
    dispatches the ops of a block of timesteps again on every trip."""
    g = small_graph()
    fused = get_backend("pallas-fused").lowered_stablehlo([g])
    assert fused.count("tpu_custom_call") == 1
    assert "stablehlo.while" not in fused

    scan = get_backend("xla-scan").lowered_stablehlo([g])
    assert "tpu_custom_call" not in scan
    assert scan.count("stablehlo.while") >= 1


def test_fused_concurrent_graphs_still_one_launch():
    """Multi-graph scenarios fuse through the leading grid dimension:
    even 3 concurrent graphs cost ONE launch (xla-scan pays one while
    loop regardless, but each trip dispatches its block's ops again)."""
    g = small_graph()
    fused = get_backend("pallas-fused").lowered_stablehlo(replicate(g, 3))
    assert fused.count("tpu_custom_call") == 1
    assert "stablehlo.while" not in fused


# --------------------------------------------------- dense dependency form
@pytest.mark.parametrize("pattern", pattern_names())
def test_dependency_table_matches_deps_lists(pattern):
    """The padded (H, W, R) table is exactly the deps() lists in sorted
    order, with dead slots masked (ragged-padding idiom)."""
    g = make_graph(width=6, height=8, pattern=pattern, iterations=2,
                   **({"radix": 3} if pattern in ("nearest", "spread")
                      else {}))
    idx, mask = g.dependency_table()
    assert idx.shape == mask.shape == (g.height, g.width,
                                       max(1, g.max_radix()))
    assert idx.dtype == np.int32 and mask.dtype == np.uint8
    for t in range(g.height):
        for i in range(g.width):
            ds = g.deps(t, i)
            got = idx[t, i][mask[t, i] != 0].tolist()
            assert got == ds, (pattern, t, i)
            # padding is column 0 under mask 0
            assert (idx[t, i][mask[t, i] == 0] == 0).all()


def test_dependency_table_padding_and_validation():
    g = make_graph(width=6, height=4, pattern="stencil", iterations=2)
    idx, mask = g.dependency_table()
    r0 = idx.shape[2]
    wide_idx, wide_mask = g.dependency_table(r0 + 2)
    assert wide_idx.shape[2] == r0 + 2
    assert (wide_idx[..., :r0] == idx).all()
    assert (wide_mask[..., r0:] == 0).all()
    with pytest.raises(ValueError, match="radix"):
        g.dependency_table(r0 - 1)
    # cached and read-only on the frozen graph
    assert g.dependency_table()[0] is idx
    with pytest.raises(ValueError):
        idx[0, 0, 0] = 7


def test_checksum_table_matches_scalar_checksum():
    g = make_graph(width=7, height=9, pattern="trivial", iterations=1)
    tab = g.checksum_table()
    assert tab.shape == (g.height, g.width)
    for t in range(g.height):
        for i in range(g.width):
            assert int(tab[t, i]) == g.checksum(t, i)


# ----------------------------------------------- bit-exact vs the scan
def test_fused_bitwise_equal_to_scan_including_kernel_slots():
    """check_outputs compares kernel slots with tolerance; for the
    elementwise kernels the fused and scan programs must in fact agree
    *bitwise* on every slot (they trace the same kernels.bodies code)."""
    fused, scan = get_backend("pallas-fused"), get_backend("xla-scan")
    for kw in (
        dict(),
        dict(kernel="memory", span_bytes=256, scratch_bytes=2048),
        dict(pattern="nearest", radix=3, imbalance=0.8, iterations=32),
        dict(width=10, output_bytes=64),
        dict(width=3, pattern="sweep"),
    ):
        g = small_graph(**kw)
        a = np.asarray(fused.run([g])[0])
        b = np.asarray(scan.run([g])[0])
        assert (a == b).all(), kw
        check_outputs(g, a, expected=execute_reference(g))


def test_fused_run_many_bitwise_equal_to_scan():
    fused, scan = get_backend("pallas-fused"), get_backend("xla-scan")
    graphs = [small_graph(pattern=p) for p in ("stencil", "sweep", "fft")]
    for a, b in zip(fused.run_many(graphs), scan.run_many(graphs)):
        assert (np.asarray(a) == np.asarray(b)).all()


# --------------------------------------------- per-launch dispatch model
def test_synthetic_per_launch_model_closed_form():
    from repro.bench import SyntheticTimer
    from repro.bench.timers import backend_dispatch_model

    assert backend_dispatch_model("pallas-fused") == "per-launch"
    assert backend_dispatch_model("pallas-fused[interpret=True]") == \
        "per-launch"
    assert backend_dispatch_model("pallas-fused[comm=onesided]") == \
        "per-launch"
    assert backend_dispatch_model("xla-scan") == "per-task"
    # lenient: unknown and malformed names default to per-task (the
    # backend-free contract of the default synthetic configuration)
    assert backend_dispatch_model("no-such-backend") == "per-task"
    assert backend_dispatch_model("garbage[[[") == "per-task"

    t = SyntheticTimer()
    g = make_graph(width=8, height=8, pattern="stencil", iterations=64)
    expect = (t.overhead_per_launch
              + g.num_tasks * t.fused_overhead_per_task
              + g.total_iterations() * t.seconds_per_iteration)
    assert t.measure("pallas-fused", [g]) == pytest.approx(expect, rel=0,
                                                           abs=0)
    # the launch cost is charged once for the whole batch, not per graph
    two = t.measure("pallas-fused", replicate(g, 2))
    assert two == pytest.approx(
        t.overhead_per_launch + 2 * (expect - t.overhead_per_launch))
    # and the fused floor undercuts the per-task charge for this graph
    assert t.measure("pallas-fused", [g]) < t.measure("xla-scan", [g])
    # the model resolves by *name*: the spec'd backend charges the exact
    # same closed form, without ever instantiating the backend
    assert t.measure("pallas-fused[comm=onesided]", [g]) == pytest.approx(
        expect, rel=0, abs=0)


# ------------------------------------------- one-sided put/signal mode
def test_onesided_option_validated():
    assert get_backend("pallas-fused[comm=onesided]").comm == "onesided"
    with pytest.raises(ValueError, match="comm"):
        get_backend("pallas-fused[comm=ring]")


@pytest.mark.parametrize("pattern", pattern_names())
def test_onesided_bitwise_equal_to_fused(pattern):
    """The communicating kernel (remote-DMA puts + semaphore waits in
    place of in-VMEM wave reads) must be bit-exact with the single-device
    fused program on every pattern."""
    kw = {"radix": 3} if pattern in ("nearest", "spread") else {}
    g = small_graph(pattern=pattern, **kw)
    a = np.asarray(get_backend("pallas-fused[comm=onesided]").run([g])[0])
    b = np.asarray(get_backend("pallas-fused").run([g])[0])
    assert (a == b).all(), pattern
    check_outputs(g, a, expected=execute_reference(g))


def test_onesided_ragged_and_run_many():
    """Ragged widths (pad columns over the mesh) and concurrent graphs
    through the per-graph communicating kernels."""
    be = get_backend("pallas-fused[comm=onesided]")
    for kw in (dict(width=10, height=6, imbalance=1.5, iterations=5),
               dict(width=3, height=5, pattern="sweep", imbalance=2.0)):
        g = small_graph(**kw)
        check_outputs(g, be.run([g])[0], expected=execute_reference(g))
    graphs = [small_graph(pattern=p) for p in ("stencil", "sweep", "fft")]
    for g, out in zip(graphs, be.run_many(graphs)):
        check_outputs(g, out, expected=execute_reference(g))


def test_onesided_lowering_single_launch_no_xla_collectives():
    """The one-sided tentpole claim, pinned structurally on the TPU
    lowering: the whole graph is still ONE kernel launch per rank with no
    dispatch loop, and no XLA collective appears anywhere in the module —
    every cross-rank byte moves through the in-kernel remote DMA
    (put/signal), never through a ppermute/all_gather rendezvous."""
    g = small_graph()
    text = get_backend("pallas-fused[comm=onesided]").lowered_stablehlo([g])
    assert text.count("tpu_custom_call") == 1
    assert "stablehlo.while" not in text
    for op in ("collective_permute", "all_gather", "all_to_all",
               "all_reduce"):
        assert op not in text, op


# ------------------------------------------------- committed baselines
def _baseline(name):
    path = os.path.join(BASELINES, f"BENCH_{name}.json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("case", ["stencil", "nearest", "spread",
                                  "nearest_x4"])
def test_committed_fused_baseline_undercuts_scan(case):
    """The acceptance claim, pinned on the committed snapshots the CI
    gate diffs against: on the same smoke sweep, pallas-fused's METG and
    its smallest-granularity point sit strictly below xla-scan's."""
    fused = _baseline(f"metg.pallas-fused.{case}")
    scan = _baseline(f"metg.xla-scan.{case}")
    assert fused["timer"] == scan["timer"] == "synthetic"
    assert fused["metg_s"] is not None and scan["metg_s"] is not None
    assert fused["metg_s"] < scan["metg_s"]

    fpts = {p["iterations"]: p for p in fused["points"]}
    spts = {p["iterations"]: p for p in scan["points"]}
    assert set(fpts) == set(spts), "baselines must share one sweep"
    smallest = min(fpts)
    assert (fpts[smallest]["granularity_s"]
            < spts[smallest]["granularity_s"])
    # the whole curve undercuts: same work, strictly less wall everywhere
    for it in fpts:
        assert fpts[it]["wall_time_s"] < spts[it]["wall_time_s"], it
