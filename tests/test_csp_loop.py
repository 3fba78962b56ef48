"""The rank program's blocked timestep loop (``backends/csp.py``): one loop
trip runs ``scanvec.BLOCK`` timesteps, the steps left over follow the
loop, and every step keeps its exchange and its kernel, in every form of
the program.  Each multi-device case runs in a child interpreter with 4
host devices, as ``test_distributed`` does."""
import re

import pytest

from repro.backends.csp import PlannedSPMDBackend
from repro.backends.scanvec import BLOCK
from test_distributed import run_sub
from test_scanvec import kernel_loops, outer_trips

K = BLOCK

FORMS = [
    "shardmap-csp",
    "shardmap-csp[comm_overlap=True]",
    "shardmap-csp[comm=onesided]",
    "shardmap-csp[comm=onesided,comm_overlap=True]",
    "shardmap-pipeline",
]


@pytest.mark.parametrize("spec,many", [(f, False) for f in FORMS]
                         + [(f, True) for f in FORMS])
def test_blocked_outputs_match_the_oracle(spec, many):
    """Heights below, at and past one block, with a tail, through
    ``prepare`` and through the combined ``prepare_many`` program of two
    graphs."""
    out = run_sub(f"""
import jax
from repro.core import make_graph, check_outputs
from repro.backends import get_backend
be = get_backend({spec!r}, devices=jax.devices()[:4])
for h in {[1, K - 1, K, K + 1, 2 * K + 3]!r}:
    g = make_graph(width=16, height=h, pattern="nearest", iterations=4,
                   radix=5)
    if {many!r}:
        gs = [g, make_graph(width=16, height=h, pattern="stencil",
                            iterations=2)]
        outs = be.prepare_many(gs)()
    else:
        gs = [g]
        outs = be.prepare(gs)()
    assert len(outs) == len(gs)
    for gi, o in zip(gs, outs):
        check_outputs(gi, o)
print("BLOCKEDOK")
""", devices=4)
    assert "BLOCKEDOK" in out


@pytest.mark.parametrize("pattern,barrier", [
    ("nearest", True), ("no_comm", True), ("no_comm", False)])
def test_every_step_keeps_its_exchange_and_kernel(pattern, barrier):
    """H = 2K + 3 over 4 ranks: one outer loop of 2 trips, K kernel loops
    in its body and 3 in the tail, and for radix-5 ``nearest`` two
    collective-permutes (one each way) under the ``exchange`` scope for
    each of those K + 3 steps.  The halo rows carry every step's kernel
    result to a neighbour, so there the exchange alone keeps the kernels;
    in ``no_comm`` no row leaves its rank, and with the barrier made the
    identity XLA folds away the kernels whose results no later step
    reads, and the count falls: the check can fail."""
    hlo = run_sub(f"""
import jax
from repro.backends import get_backend
from repro.core import make_graph
if not {barrier!r}:
    jax.lax.optimization_barrier = lambda x: x
be = get_backend("shardmap-csp", devices=jax.devices()[:4])
g = make_graph(width=16, height={2 * K + 3}, pattern={pattern!r},
               iterations=4, **({{"radix": 5}} if {pattern!r} == "nearest"
                                else {{}}))
print(be.lowered_hlo([g])[0])
""", devices=4)
    assert outer_trips(hlo) == 2
    permutes = [re.search(r'op_name="([^"]*)"', line).group(1)
                for line in hlo.splitlines()
                if re.search(r" collective-permute(-start)?\(", line)]
    if not barrier:
        assert kernel_loops(hlo) < K + 3
        return
    assert kernel_loops(hlo) == K + 3
    assert len(permutes) == (2 * (K + 3) if pattern == "nearest" else 0)
    assert all("/exchange/" in name for name in permutes), permutes


@pytest.mark.parametrize("height,trips,steps", [
    (1000, 125, 8),   # the benchmark's height: no tail
    (1003, 125, 8),   # a tail of 3
    (19, 2, 8),       # 2K + 3
    (5, 1, 5),        # below one block: one trip of every step
    (1, 1, 1),
    (0, 0, 1),        # the double-buffered loop at H = 1: no trip
])
def test_loop_shape(height, trips, steps):
    """The counter of the blocked rank loop: trips and steps per trip."""
    assert PlannedSPMDBackend.loop_shape(height) == (trips, steps)
