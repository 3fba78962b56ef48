"""Scan backend: a compiled loop over timesteps, columns vectorized.

Analogue of the paper's vectorized on-node runtimes (OpenMP forall /
MPI+OpenMP inner loop).  One trip of the loop runs a block of ``BLOCK``
consecutive timesteps, each the shared task body unchanged, so the loop's
control and the slice of the dependency and iteration tables are paid once
per block; the ``H % BLOCK`` steps left over run as straight-line code
after the loop.  Compile cost does not grow with graph height (unlike
xla-static), at the price of a loop-carried schedule: every step finishes
its kernel before the next step's combine starts.
"""
from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import TaskGraph
from . import body
from .base import StackedProgramBackend, register_backend

# Timesteps one trip of the loop runs (capped at the graph's height).  On a
# TPU v5e at W=128, 8 ran the benchmark's stencil sweep faster than 4 or 16
# at 2 to 8 iterations a task, where METG(50%) falls (PERF.md).
BLOCK = 8


def loop_shape(height: int) -> Tuple[int, int]:
    """(trips, steps per trip) of the timestep loop over ``height`` steps;
    the ``height - trips * steps`` steps left over follow the loop.  A
    loop over no steps makes no trip."""
    steps = max(1, min(BLOCK, height))
    return height // steps, steps


def blocked_tables(steps: int, *tables: np.ndarray):
    """The timestep numbers and each ``(H, ...)`` table, split once on the
    host into the loop's ``(trips, K, ...)`` blocks over the first
    ``steps`` timesteps and the rows after them, which follow the loop:
    ``((ts, *tables), (ts, *tables))`` of numpy arrays."""
    trips, k = loop_shape(steps)
    n = trips * k
    tables = (np.arange(len(tables[0]), dtype=np.uint32),) + tables
    return (tuple(a[:n].reshape((trips, k) + a.shape[1:]) for a in tables),
            tuple(a[n:] for a in tables))


def timestep_loop(step: Callable, init, blocks, tail):
    """Run ``step(payload, *row) -> payload`` over every timestep of
    ``blocks`` and ``tail``, pytrees of tables split as
    ``blocked_tables`` splits them, ``row`` each table's row at the step:
    one loop trip a block, the tail after.

    Each step's payload passes an optimization barrier before the next
    step reads it: the next combine reads one column of it, and without
    the barrier XLA would drop the kernel of every step but a block's
    last, whose result only the loop carry keeps."""

    def run(payload, tables):
        for j in range(jax.tree.leaves(tables)[0].shape[0]):
            payload = jax.lax.optimization_barrier(
                step(payload, *jax.tree.map(lambda a: a[j], tables)))
        return payload

    payload, _ = jax.lax.scan(lambda p, xs: (run(p, xs), None), init,
                              blocks)
    return run(payload, tail)


@register_backend("xla-scan")
class ScanBackend(StackedProgramBackend):
    paradigm = "compiled timestep loop (OpenMP-forall analogue)"

    loop_shape = staticmethod(loop_shape)

    def _build(self, graphs: Sequence[TaskGraph]):
        """One program looping over each graph in turn (independent
        execution)."""
        tables = [jax.tree.map(jnp.asarray, blocked_tables(
            g.height, *body.graph_static_inputs(g))) for g in graphs]

        def program(all_tables):
            outs = []
            for g, (blocks, tail) in zip(graphs, all_tables):
                init = jnp.zeros((g.width, g.payload_elems), jnp.float32)

                def step(payload, t, mat, it):
                    return body.timestep(g, t, payload, mat, it)

                outs.append(timestep_loop(step, init, blocks, tail))
            return outs

        return jax.jit(program), tables

    def _build_stacked(self, graphs: Sequence[TaskGraph]):
        """One loop over a stacked (graph, width) payload — the concurrent
        form: all graphs advance in the same compiled timestep (multi-graph
        scenarios, paper Fig 9d).  None if the graphs cannot share a body."""
        if not body.stackable(graphs):
            return None
        g0 = graphs[0]
        mats, iters = body.stacked_static_inputs(graphs)
        blocks, tail = jax.tree.map(jnp.asarray, blocked_tables(
            g0.height,
            mats.transpose(1, 0, 2, 3),  # (H, G, W, W)
            iters.transpose(1, 0, 2)))   # (H, G, W)

        def program(blocks, tail):
            init = jnp.zeros((len(graphs), g0.width, g0.payload_elems),
                             jnp.float32)

            def step(payload, t, mat, it):
                return jax.vmap(
                    lambda p, m, iv: body.timestep(g0, t, p, m, iv)
                )(payload, mat, it)

            return timestep_loop(step, init, blocks, tail)

        return jax.jit(program), blocks, tail
