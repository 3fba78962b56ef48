"""The xla-scan backend's blocked timestep loop: one loop trip runs
``BLOCK`` timesteps, the steps left over follow the loop, and every step
keeps its kernel.

XLA may drop a step's kernel without changing a single output: the next
step reads only the ``combined`` column of the payload, so only the loop
carry keeps a kernel result alive.  The outputs cannot show the loss, so
the compiled HLO is counted here: one kernel ``while`` (the masked
``fori_loop``) per timestep of a block and of the tail."""
import re

import jax
import pytest

from repro.backends import get_backend
from repro.backends.scanvec import BLOCK, ScanBackend
from repro.core import check_outputs, make_graph

K = BLOCK


def graph(height, pattern="stencil", width=8):
    return make_graph(width=width, height=height, pattern=pattern,
                      iterations=4, **({"radix": 3} if pattern == "nearest"
                                       else {}))


def whiles(hlo):
    """(op_name, known trip count) of every ``while`` in optimized HLO."""
    return [(re.search(r'op_name="([^"]*)"', line).group(1),
             int(re.search(r'"known_trip_count":\{"n":"(\d+)"', line).group(1)))
            for line in hlo.splitlines() if " while(" in line]


def kernel_loops(hlo):
    return sum("/kernel/" in name for name, _ in whiles(hlo))


def outer_trips(hlo):
    trips, = [n for name, n in whiles(hlo) if "/kernel/" not in name]
    return trips


@pytest.mark.parametrize("barrier", [True, False])
def test_every_step_keeps_its_kernel(monkeypatch, barrier):
    """H = 2K + 3: K kernel loops in the loop's body and 3 in the tail,
    besides the one outer loop.  With the barrier made the identity, XLA
    folds away the kernels whose results no later step reads, and the
    count falls: the check can fail."""
    if not barrier:
        monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    g = graph(2 * K + 3)
    hlo, = get_backend("xla-scan").lowered_hlo([g])
    assert outer_trips(hlo) == 2
    if barrier:
        assert kernel_loops(hlo) == K + 3
    else:
        assert kernel_loops(hlo) < K + 3


@pytest.mark.parametrize("concurrent", [False, True])
@pytest.mark.parametrize("pattern", ["stencil", "nearest"])
@pytest.mark.parametrize("height", [1, K - 1, K, K + 1, 2 * K + 3])
def test_blocked_outputs_match_the_oracle(height, pattern, concurrent):
    """Heights below, at and past one block, with a tail, through
    ``prepare`` and through the stacked ``prepare_many`` of two graphs."""
    be = get_backend("xla-scan")
    if concurrent:
        gs = [graph(height, pattern), graph(height, "stencil")]
        outs = be.prepare_many(gs)()
    else:
        gs = [graph(height, pattern)]
        outs = be.prepare(gs)()
    assert len(outs) == len(gs)
    for g, out in zip(gs, outs):
        check_outputs(g, out)


@pytest.mark.parametrize("height,trips,steps", [
    (1000, 125, 8),   # the benchmark's height: no tail
    (1003, 125, 8),   # a tail of 3
    (19, 2, 8),       # 2K + 3
    (5, 1, 5),        # below one block: one trip of every step
    (1, 1, 1),
])
def test_loop_shape(height, trips, steps):
    """The counter of the blocked loop: trips and steps per trip."""
    assert ScanBackend.loop_shape(height) == (trips, steps)


@pytest.mark.parametrize("height", [1000, 1003])
def test_compiled_trip_count(height):
    """The outer ``while``'s trip count in the compiled program is the
    counter's, for the benchmark's height and for a tail."""
    trips, steps = ScanBackend.loop_shape(height)
    hlo, = get_backend("xla-scan").lowered_hlo([graph(height)])
    assert outer_trips(hlo) == trips
    assert kernel_loops(hlo) == steps + height % steps
