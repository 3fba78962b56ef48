"""The communication layer's metric: ``collective_share.fine`` read from a
reduced trace window, and the cells it is read in."""
import json
import os

import pytest

from chipbench import harness
from chipbench import trace as T

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "nearest5-compute.shardmap-csp.w512.4chip"


def reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        metric, = [m for m in json.load(f)["per_layer"]
                   if m["name"] == "collective_share.fine"]
    return harness.load_reader(metric, REPO)


def windows(busy_ns, collective_ns):
    w = T.Window("fine", 0.0, 2e6, chips=4, busy_ns=busy_ns,
                 collective_ns=collective_ns)
    point = harness.Point(1, None, 512 * 1000, 1.0)
    return {"fine": harness.Traced(point, 3, 1000, w)}


@pytest.mark.parametrize("busy_ns,collective_ns,share", [
    (1.5e6, 0.6e6, 40.0),
    (1.0e6, 0.0, 0.0),
    (0.0, 0.0, None),  # no program ran in the window
])
def test_collective_share_is_collective_time_over_busy_time(
        busy_ns, collective_ns, share):
    got = reader().read(windows(busy_ns, collective_ns))
    assert got == (None if share is None else pytest.approx(share))


@pytest.mark.parametrize("cell,reads", [
    (CELL, True),
    ("stencil-compute.xla-scan.w128", False),
    ("stencil-compute.pallas-fused.w56", False),
])
def test_collective_share_is_read_in_the_cells_that_cross_chips(cell, reads):
    c = harness.load_cell(cell, REPO)
    assert ("collective_share.fine" in [m["name"] for m in c.per_layer]) is reads
    assert (c.chips == 4) is reads
