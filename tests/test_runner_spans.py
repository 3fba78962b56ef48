"""Every runner splits a graph run into launch, wait and readback host
spans, recorded by ``jax.profiler`` on the CPU here; the task step's
named scopes reach the compiled program's metadata."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import get_backend
from repro.backends.base import LAUNCH, READBACK, WAIT, Runner, in_turn
from repro.core import check_outputs, make_graph

PHASES = [LAUNCH, WAIT, READBACK]


def graphs(n):
    return [make_graph(width=4, height=6, pattern=p, iterations=2)
            for p in ("stencil", "nearest")[:n]]


def runner_spans(runner, runs, tmp_path):
    """Per host thread that holds any, the runner spans of ``runs`` calls
    of ``runner`` under a profiler session, as (name, start, end)."""
    runner()  # warm: no compile in the session
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(runs):
            runner()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted((e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name in PHASES)
            if evs:
                out.append(sorted(evs, key=lambda e: e[1]))
    return out


@pytest.mark.parametrize("spec,ngraphs,many,rounds", [
    ("xla-scan", 1, False, 1),
    ("pallas-fused[interpret=True]", 1, False, 1),
    ("xla-scan", 2, True, 1),            # one stacked program
    ("xla-static", 2, False, 1),         # one program for both graphs
    ("host-dynamic", 2, False, 2),       # each graph in turn
    ("host-dynamic", 2, True, 1),        # wavefronts interleaved
    ("shardmap-csp", 2, False, 2),       # one program per graph, in turn
    ("shardmap-csp", 2, True, 1),        # one combined program
])
def test_each_run_spans_launch_wait_readback_in_order(tmp_path, spec, ngraphs,
                                                      many, rounds):
    gs = graphs(ngraphs)
    be = get_backend(spec)
    runner = be.prepare_many(gs) if many else be.prepare(gs)
    for g, out in zip(gs, runner()):
        check_outputs(g, out)
    runs = 3
    lines = runner_spans(runner, runs, tmp_path)
    assert len(lines) == 1, "the spans lie on the calling thread alone"
    spans = lines[0]
    assert [n for n, _, _ in spans] == PHASES * rounds * runs
    for (_, _, end), (_, start, _) in zip(spans, spans[1:]):
        assert end <= start, "the phases are disjoint"


def test_in_turn_reads_each_run_back_before_the_next_launch():
    log = []

    def runner(k):
        def launch():
            log.append(("launch", k))
            return [jnp.full((2,), k)]

        def readback(outs):
            log.append(("readback", k))
            return [np.asarray(o) for o in outs]

        return Runner(launch, readback)

    one = runner(0)
    assert in_turn([one]) is one
    outs = in_turn([runner(1), runner(2)])()
    assert [o.tolist() for o in outs] == [[1, 1], [2, 2]]
    assert log == [("launch", 1), ("readback", 1),
                   ("launch", 2), ("readback", 2)]


def test_named_scopes_name_the_combine_fusion_and_the_kernel_loop():
    text, = get_backend("xla-scan").lowered_hlo(graphs(1))
    ops = dict(re.findall(r'%([^\s=]+) = [^\n]*?op_name="([^"]*)"', text))
    for scope in ("combine", "checksum", "kernel", "payload"):
        assert any(f"/{scope}/" in o for o in ops.values()), scope
    assert any("fusion" in n and "/combine/" in o
               for n, o in ops.items()), "the combine's fusion carries its scope"
    assert any(n.startswith("while") and "/kernel/" in o
               for n, o in ops.items()), "the kernel's fori_loop carries its scope"
