"""The trace reduction: on hand-made planes, and on a small trace
recorded on a TPU v5e (``fixtures/``)."""
import gzip
import os
from types import SimpleNamespace as NS

import pytest

from chipbench import trace as T

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def profile(host_events, devices):
    """Host thread events, and per chip id its (modules, ops) events."""
    planes = [NS(name="/host:CPU", lines=[
        NS(name="main", events=host_events),
        NS(name="other", events=[ev("elsewhere", 0, 10_000)])])]
    for chip, (modules, ops) in devices.items():
        planes.append(NS(name=f"/device:TPU:{chip}", lines=[
            NS(name=T.MODULE_LINE, events=modules),
            NS(name=T.OP_LINE, events=ops)]))
    planes.append(NS(name="/device:TPU:0 SparseCore", lines=[]))
    return NS(planes=planes)


def test_union_and_gaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.gaps([(0, 3), (5, 8)], -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert T.gaps([(0, 10)], 2, 5) == []


def test_gaps_go_to_the_innermost_host_span():
    spans = [(0, 100, "run"), (10, 20, "dispatch"), (30, 40, "copy"),
             (50, 60, "block"), (200, 300, "check")]
    assert T.attribute(spans, [5, 15, 25, 35, 70, 150, 250]) == [
        "run", "dispatch", "run", "copy", "run", "no host span", "check"]


def test_collectives_are_named_as_xla_names_them():
    for name in ("collective-permute-start.2", "collective-permute-done",
                 "all-gather.1", "all-reduce-start", "reduce-scatter.3"):
        assert T.COLLECTIVE.match(name)
    for name in ("fusion.12", "copy.3", "while.1", "custom-call.pallas"):
        assert not T.COLLECTIVE.match(name)


def test_op_names_are_short():
    assert T.op_name("%fusion.8 = u32[128]{0:T(128)S(1)} fusion(u32[128] "
                     "%a), kind=kLoop") == "fusion.8"
    assert T.op_name('%program.1 = f32[56,5]{1,0} custom-call(s32[1] %copy),'
                     ' custom_call_target="tpu_custom_call"'
                     ) == "program.1 tpu_custom_call"


def test_self_time_takes_nested_operations_out():
    evs = [("while", 0, 100), ("body", 10, 40), ("body", 50, 90),
           ("inner", 60, 70), ("after", 100, 120)]
    assert T.self_times(evs) == {"while": 30, "body": 60, "inner": 10,
                                 "after": 20}


def test_reduce_profile_on_hand_made_planes():
    host = [ev("chipbench.window.fine", 100, 1000),
            ev("chipbench.run", 100, 400), ev("chipbench.run", 600, 400)]
    chip0 = ([ev("jit_program", 150, 300), ev("jit_program", 700, 100),
              ev("jit_program", 5000, 10)],
             [ev("%while.1 = (s32[]) while((s32[]) %t)", 150, 300),
              ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 160, 140),
              ev("%collective-permute-start.1 = f32[2] collective-permute-"
                 "start(f32[2] %p)", 300, 100),
              ev("%fusion.2 = f32[8] fusion(f32[8] %b)", 700, 100),
              ev("%outside = f32[8] fusion(f32[8] %b)", 5000, 10)])
    chip1 = ([ev("jit_program", 150, 500), ev("jit_program", 650, 200)],
             [ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 150, 500),
              ev('%program.1 = f32[8] custom-call(f32[8] %a), '
                 'custom_call_target="tpu_custom_call"', 650, 200)])
    prof = profile(host, {0: chip0, 1: chip1})
    w = T.reduce_profile(prof, [0, 1], ops=True)["fine"]
    assert w.window_s == pytest.approx(1e-6)
    assert w.busy_ns == pytest.approx((400 + 700) / 2)
    assert w.op_ns == pytest.approx({
        "while.1": 30, "fusion.1": 320, "collective-permute-start.1": 50,
        "fusion.2": 50, "program.1 tpu_custom_call": 100})
    assert w.collective_ns == pytest.approx(50)
    # chip 0 idles 100-150, 450-700 and 800-1100; chip 1 100-150, 850-1100
    assert sum(w.gap_ns.values()) == pytest.approx((600 + 300) / 2)
    assert set(w.gap_ns) <= {"chipbench.run", "no host span"}
    lean = T.reduce_profile(prof, [0, 1])["fine"]
    assert lean.busy_ns == w.busy_ns and lean.op_ns == {}


def test_a_chip_missing_from_the_trace_is_an_error():
    host = [ev("chipbench.window.fine", 0, 10)]
    with pytest.raises(ValueError, match="no device lines for chips"):
        T.reduce_profile(profile(host, {0: ([], [])}), [0, 1])


def test_a_recorded_trace_reduces_to_its_windows(tmp_path):
    """A trace recorded on one TPU v5e: ``pallas-fused`` at W=56, H=1000,
    a window of one second at 1 iteration and one of 3 s at 2048."""
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(os.path.join(FIXTURES, "pallas-fused-w56.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    prof = T.load(str(path))
    windows = T.reduce_profile(prof, [0], ops=True)
    assert set(windows) == {"fine", "coarse"}
    fine, coarse = windows["fine"], windows["coarse"]
    assert 0.99 < fine.window_s < 1.01 and 3.0 < coarse.window_s < 3.1
    assert 0 < fine.busy_s < fine.window_s and 0 < coarse.busy_s < coarse.window_s
    # at 2048 iterations the kernel fills the window; at 1 the host does
    assert coarse.busy_s / coarse.window_s > 0.99
    assert fine.busy_s / fine.window_s < 0.5
    kernel = "program.1 tpu_custom_call"
    assert max(fine.op_ns, key=fine.op_ns.get) == kernel
    assert sum(fine.op_ns.values()) == pytest.approx(fine.busy_ns, rel=0.01)
    assert fine.collective_ns == 0
    assert sum(fine.gap_ns.values()) == pytest.approx(
        fine.window_s * 1e9 - fine.busy_ns, rel=1e-6)
    assert max(fine.gap_ns, key=fine.gap_ns.get) == "np.asarray(jax.Array)"
    with pytest.raises(ValueError):
        T.reduce_profile(prof, [0, 1])
