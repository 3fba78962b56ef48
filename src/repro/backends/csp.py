"""CSP backend: explicit SPMD ranks exchanging messages per timestep.

Analogue of the paper's MPI implementation (Listing 2): columns are
distributed over device ranks via ``shard_map``; every timestep each rank
receives the payloads its local tasks depend on, executes its tasks, and
sends its outputs.  All planning — halo sizing, ragged-width padding,
dependence re-indexing, mode selection — lives in
``repro.dist.collectives.CommPlan``; this module only owns execution.

``PlannedSPMDBackend`` is the shared rank-program machinery: any backend
that blocks graph columns over a mesh axis and moves payloads with a
``CommPlan`` (CSP over ``cols``, the pipeline backend over ``stage``)
subclasses it and picks an axis + mode preference.

Like MPI CSP, communication and computation strictly alternate by
default — no overlap, no task parallelism — which is exactly why the
paper finds MPI loses its advantage under imbalance and heavy
communication (§V-F/G).  ``comm_overlap=True`` switches the program to
the double-buffered form (the MPI_Isend/Irecv analogue): the loop carry
holds the *pre-exchanged* context for the current timestep, and each
step issues the next timestep's exchange immediately after producing its
payload — ahead of the next kernel body — so XLA's async collectives may
run while compute proceeds.  The final timestep runs after the loop
(its payload needs no exchange), so both forms issue exactly H
exchanges, and the exchanged values are identical — conformance is
bit-exact either way.

``comm="onesided"`` drops the rendezvous entirely (the NVSHMEM-style
put/signal idiom): the loop carry holds the plan's receive buffers and
signal counters (``CommPlan.onesided_state``), each step's producers
push their dependency rows and raise the consumer's flag
(``onesided_push``), and consumers assemble their context through the
masked ``signal_wait_until`` (``onesided_wait``) instead of joining a
collective.  Composes with ``comm_overlap`` (the wait for step t+1 is
issued right after step t's push, ahead of the next kernel body) and
with the combined multi-graph program; bit-exact with every other mode.

Every form runs one loop, xla-scan's ``scanvec.timestep_loop``: a trip
runs a block of ``scanvec.BLOCK`` timesteps, each with its exchange and
its kernel, so the loop's control and the slice of the tables are paid
once a block.  The tables are split into those blocks once, on the host,
and placed on the mesh when the runner is prepared.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compat import pcast, shard_map
from ..core.graph import TaskGraph
from ..dist import collectives as CC
from ..launch.mesh import make_mesh, rank_mesh
from . import body, scanvec
from .base import Backend, Runner, in_turn, register_backend

AXIS = "cols"


class PlannedSPMDBackend(Backend):
    """Columns blocked over one mesh axis; movement per a ``CommPlan``.

    Ragged widths are handled by the plan's dead-column padding, so any
    graph width runs on any rank count (including width < ndev).  The
    ranks are ``mesh``'s devices; without one, see ``launch.mesh.rank_mesh``.
    """

    axis = AXIS
    prefer_ring = False

    loop_shape = staticmethod(scanvec.loop_shape)

    def __init__(self, mesh: Mesh | None = None, comm: str = "auto",
                 comm_overlap: bool = False):
        if mesh is None:
            mesh = rank_mesh(self.axis)
        if comm not in CC.MODES:
            raise ValueError(f"unknown comm mode {comm!r}; known: {CC.MODES}")
        self.mesh = mesh
        self.comm = comm
        self.comm_overlap = bool(comm_overlap)
        self.ndev = mesh.shape[self.axis]

    @classmethod
    def device_options(cls, devices, options):
        """One rank per device: a 1-D mesh of ``devices`` on the rank axis."""
        return {"mesh": make_mesh((len(devices),), (cls.axis,), devices)}

    def plan(self, graph: TaskGraph) -> CC.CommPlan:
        return CC.plan_comm(graph, self.ndev, self.axis, comm=self.comm,
                            prefer_ring=self.prefer_ring,
                            comm_overlap=self.comm_overlap)

    def prepare(self, graphs: Sequence[TaskGraph]):
        return in_turn([self._runner([g]) for g in graphs])

    def _table_specs(self):
        """How a plan's split tables lie on the mesh, as ``_tables`` gives
        them: each rank holds its own columns of every timestep, and every
        rank the timestep numbers."""
        a = self.axis
        return ((P(), P(None, None, a, None), P(None, None, a)),
                (P(), P(None, a, None), P(None, a)))

    def _tables(self, plan: CC.CommPlan):
        """``plan``'s (timesteps, local_mats, iters), split on the host into
        the rank program's loop blocks and the rows after them.  The
        double-buffered form's loop stops a timestep short: its last step
        runs after the loop."""
        return scanvec.blocked_tables(len(plan.iters) - plan.comm_overlap,
                                      plan.local_mats, plan.iters)

    def _resident(self, plan: CC.CommPlan):
        """``plan``'s split tables placed on the mesh once, in the shardings
        the rank program takes them in, so that a run moves no table."""
        return jax.tree.map(
            lambda a, spec: jax.device_put(a, NamedSharding(self.mesh, spec)),
            self._tables(plan), self._table_specs())

    def _rank_step(self, graph: TaskGraph, plan: CC.CommPlan):
        """``graph``'s part of the rank program (inside ``shard_map``): the
        carry before the first timestep, whose first entry holds the
        payload rows, the step ``(carry, (t, mat, iters)) -> carry``, and
        the task step ``(ctx, t, mat, iters) -> payload`` the
        double-buffered forms end with."""
        cols = plan.local_cols()
        dynamic = plan.local == 1  # true per-rank loops can stop early

        def run(ctx, t, mat, it):
            return body.timestep(graph, t, ctx, mat, it, cols=cols,
                                 dynamic=dynamic)

        # the carry becomes device-varying after the first exchange; mark
        # it so from the start (shard_map vma typing)
        varying = lambda a: pcast(a, (self.axis,), to="varying")
        payload0 = varying(jnp.zeros((plan.local, graph.payload_elems),
                                     jnp.float32))
        if plan.mode == "onesided":
            recv0, sig0 = map(varying,
                              plan.onesided_state(graph.payload_elems))
            if plan.comm_overlap:
                # put/signal double buffering: step t pushes its payload,
                # then at once issues step t+1's masked wait, ahead of the
                # next kernel body
                def step(carry, row):
                    ctx, recv, sig = carry
                    new = run(ctx, *row)
                    recv, sig = plan.onesided_push(new, recv, sig)
                    ctx = plan.onesided_wait(recv, sig, row[0] + 1, new)
                    return ctx, recv, sig

                return ((plan.onesided_wait(recv0, sig0, 0, payload0),
                         recv0, sig0), step, run)

            def step(carry, row):
                payload, recv, sig = carry
                new = run(plan.onesided_wait(recv, sig, row[0], payload), *row)
                return (new,) + plan.onesided_push(new, recv, sig)

            return (payload0, recv0, sig0), step, run

        if plan.comm_overlap:
            # double-buffered: the carry holds this step's already-exchanged
            # context, and each step issues the next step's exchange ahead
            # of the next kernel body
            return ((plan.exchange(payload0),),
                    lambda carry, row: (plan.exchange(run(carry[0], *row)),),
                    run)
        return ((payload0,),
                lambda carry, row: (run(plan.exchange(carry[0]), *row),), run)

    def _program(self, graphs: Sequence[TaskGraph]):
        """The jitted rank program of ``graphs`` and their plans: each step
        of its loop exchanges and executes one timestep of every graph, so
        XLA may overlap one graph's exchange with another's kernels (the
        rank-parallel form of task parallelism).  It takes each plan's
        ``_tables`` in ``_table_specs``.

        The loop is ``scanvec.timestep_loop``: a block of K timesteps a
        trip.  The double-buffered forms issue timestep 0's exchange before
        the loop and run their last timestep after it, as its payload needs
        no exchange, so every form issues exactly H exchanges."""
        plans = [self.plan(g) for g in graphs]

        def rank_program(tables):
            parts = [self._rank_step(g, p) for g, p in zip(graphs, plans)]

            def step(carry, *rows):
                return tuple(s(c, row) for (_, s, _), c, row
                             in zip(parts, carry, rows))

            blocks = tuple(b for b, _ in tables)
            tail = tuple(t for _, t in tables)
            if self.comm_overlap:
                last = jax.tree.map(lambda a: a[-1], tail)
                tail = jax.tree.map(lambda a: a[:-1], tail)
            carry = scanvec.timestep_loop(
                step, tuple(init for init, _, _ in parts), blocks, tail)
            if self.comm_overlap:
                return tuple(run(c[0], *row) for (_, _, run), c, row
                             in zip(parts, carry, last))
            return tuple(c[0] for c in carry)

        shmapped = shard_map(
            rank_program,
            mesh=self.mesh,
            in_specs=(tuple(self._table_specs() for _ in plans),),
            out_specs=tuple(P(self.axis, None) for _ in plans),
        )
        return jax.jit(shmapped), plans

    def _compile(self, graphs: Sequence[TaskGraph]):
        fn, plans = self._program(graphs)
        tables = tuple(self._resident(p) for p in plans)
        return fn.lower(tables).compile(), plans, tables

    def _runner(self, graphs: Sequence[TaskGraph]) -> Runner:
        compiled, plans, tables = self._compile(graphs)
        return Runner(lambda: compiled(tables),
                      lambda outs: [p.trim(np.asarray(o))
                                    for p, o in zip(plans, outs)])

    @staticmethod
    def _lockstep(graphs: Sequence[TaskGraph]) -> bool:
        """Can ``graphs`` share one rank program?  They need a common
        height, the shared clock; one graph gains nothing from it."""
        return len(graphs) > 1 and len({g.height for g in graphs}) == 1

    def prepare_many(self, graphs: Sequence[TaskGraph]):
        graphs = list(graphs)
        if not self._lockstep(graphs):
            return self.prepare(graphs)
        return self._runner(graphs)

    def lowered_hlo(self, graphs: Sequence[TaskGraph]) -> List[str]:
        graphs = list(graphs)
        groups = [graphs] if self._lockstep(graphs) else [[g] for g in graphs]
        return [self._compile(gs)[0].as_text() for gs in groups]


@register_backend("shardmap-csp")
class CSPBackend(PlannedSPMDBackend):
    paradigm = "explicit SPMD message passing (MPI CSP analogue)"
