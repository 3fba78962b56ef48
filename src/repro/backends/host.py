"""Host-dynamic backend: one dispatch per task from the Python host.

Analogue of the paper's dynamic, centrally-scheduled systems (Dask, Spark,
Swift/T): every task is a separate device invocation issued by the host,
with payload gather/scatter through host memory.  This is the high-overhead
end of the METG spectrum — per-task cost is dominated by dispatch, exactly
like the paper's §V-C findings for data-analytics systems.

Two executor schedules (paper §V-G, the load-imbalance study):

``schedule="static"``
    Column-order dispatch — each wavefront's tasks issue in static column
    ownership order, the per-task analogue of an MPI rank walking its
    block.

``schedule="steal"``
    Work-stealing dispatch — each wavefront's tasks issue in the greedy
    claim order of ``core.schedule.steal_schedule``: whenever a simulated
    worker goes idle it claims the longest unclaimed task, so imbalanced
    wavefronts re-pack instead of waiting on the slowest static block.
    Values are bit-identical to static (only issue *order* changes);
    the deterministic fake clock (``SyntheticTimer(workers=...)``) charges
    the matching makespan, which is where the mitigation shows up.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import CHECKSUM_MOD, TaskGraph
from ..core.schedule import steal_schedule
from . import body
from .base import Backend, Runner, in_turn, register_backend

SCHEDULES = ("static", "steal")


@register_backend("host-dynamic")
class HostBackend(Backend):
    paradigm = "dynamic per-task host dispatch (Dask/Spark analogue)"

    def __init__(self, schedule: str = "static", workers: int = 4):
        if schedule not in SCHEDULES:
            raise ValueError(
                f"unknown schedule {schedule!r}; known: {SCHEDULES}")
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        self.schedule = schedule
        self.workers = workers
        self.sched_policy = "steal" if schedule == "steal" else "static"

    def _wavefront_order(self, graph: TaskGraph, iters: np.ndarray,
                         t: int) -> List[int]:
        """Column issue order for timestep ``t`` under this schedule."""
        if self.schedule == "static":
            return list(range(graph.width))
        return steal_schedule(iters[t].astype(np.float64), self.workers)[0]

    def _wavefront_orders(self, graph: TaskGraph,
                          iters: np.ndarray) -> List[List[int]]:
        """Issue order of every wavefront, precomputed at prepare time so
        the timed runner pays dispatch only (the claim order is a pure
        function of the graph — recomputing it per run would charge the
        steal schedule scheduling overhead static never pays)."""
        return [self._wavefront_order(graph, iters, t)
                for t in range(graph.height)]

    def dispatch_order(self, graph: TaskGraph) -> List[Tuple[int, int]]:
        """The full (t, i) issue sequence ``prepare`` walks (pure, no jax).

        Wavefronts issue strictly in timestep order — all dependencies
        live in t-1, so any within-wavefront permutation is legal — which
        is what the work-stealing property tests assert.
        """
        _, iters = body.graph_static_inputs(graph)
        return [(t, i)
                for t, order in enumerate(self._wavefront_orders(graph, iters))
                for i in order]

    def _dispatch_timestep(self, g: TaskGraph, fn, iters, store, t: int,
                           radix: int, order: Sequence[int]):
        """Issue every task of timestep ``t`` (and retire timestep t-2)."""
        for i in order:
            deps = g.deps(t, i)
            pads = jnp.zeros((radix, g.payload_elems), jnp.float32)
            if deps:
                stacked = jnp.stack([store[(t - 1, j)] for j in deps])
                pads = pads.at[: len(deps)].set(stacked)
            store[(t, i)] = fn(
                jnp.uint32(t),
                jnp.uint32(i),
                jnp.int32(iters[t, i]),
                pads,
                jnp.int32(len(deps)),
            )
        for i in range(g.width):
            store.pop((t - 2, i), None)

    def prepare(self, graphs: Sequence[TaskGraph]):
        """One ``Runner`` per graph, run in turn; a graph's launch span
        holds its whole dispatch loop."""
        return in_turn([self._prepare_one(g) for g in graphs])

    def _prepare_one(self, g: TaskGraph) -> Runner:
        fn = self._compile_task(g)
        _, iters = body.graph_static_inputs(g)
        orders = self._wavefront_orders(g, iters)
        radix = max(1, g.max_radix())

        def launch() -> List[jax.Array]:
            store: Dict[Tuple[int, int], jax.Array] = {}
            for t in range(g.height):
                self._dispatch_timestep(g, fn, iters, store, t, radix,
                                        orders[t])
            return [jnp.stack([store[(g.height - 1, i)]
                               for i in range(g.width)])]

        return Runner(launch)

    def prepare_many(self, graphs: Sequence[TaskGraph]):
        """Concurrent execution: wavefronts of the graphs interleave.

        A dynamic scheduler with several ready task graphs issues whichever
        tasks are runnable; here the host walks timesteps outermost and
        dispatches every graph's timestep-t tasks before any graph's t+1,
        so the async JAX dispatch queue holds work from all graphs at once
        (the paper's task-parallelism scenario, Fig 9d).
        """
        graphs = list(graphs)
        if len(graphs) <= 1:
            return self.prepare(graphs)
        task_fns = [self._compile_task(g) for g in graphs]
        statics = [body.graph_static_inputs(g) for g in graphs]
        radii = [max(1, g.max_radix()) for g in graphs]
        orders = [self._wavefront_orders(g, iters)
                  for g, (mats, iters) in zip(graphs, statics)]

        def launch() -> List[jax.Array]:
            stores: List[Dict[Tuple[int, int], jax.Array]] = [
                {} for _ in graphs]
            for t in range(max(g.height for g in graphs)):
                for g, fn, (mats, iters), store, radix, g_orders in zip(
                        graphs, task_fns, statics, stores, radii, orders):
                    if t < g.height:
                        self._dispatch_timestep(g, fn, iters, store, t, radix,
                                                g_orders[t])
            return [jnp.stack([store[(g.height - 1, i)]
                               for i in range(g.width)])
                    for g, store in zip(graphs, stores)]

        return Runner(launch)

    @staticmethod
    def _compile_task(graph: TaskGraph):
        """One jitted function per graph spec, shared by all its tasks.

        Task duration is a *traced* argument so imbalanced graphs do not
        trigger recompiles (the kernel loop uses a dynamic trip count).
        """
        radix = max(1, graph.max_radix())

        @jax.jit
        def task(t, i, iters, inputs, nvalid):
            mask = jnp.arange(radix) < nvalid
            acc = (inputs[:, 3].astype(jnp.uint32) * mask.astype(jnp.uint32)).sum()
            acc = (acc % jnp.uint32(CHECKSUM_MOD))[None]
            base = body.checksum_vec(t, i[None])
            combined = (base + acc) % jnp.uint32(CHECKSUM_MOD)
            result = body.run_kernel_vec(
                graph.kernel, iters[None], acc, graph.kernel.iterations,
                dynamic=True,
            )
            head = jnp.stack([
                t.astype(jnp.float32),
                i.astype(jnp.float32),
                base[0].astype(jnp.float32),
                combined[0].astype(jnp.float32),
                result[0],
            ])
            if graph.payload_elems > 5:
                ballast = jnp.broadcast_to(result, (graph.payload_elems - 5,))
                return jnp.concatenate([head, ballast])
            return head

        return task
