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
communication (§V-F/G).  ``comm_overlap=True`` switches both the
single-graph and the combined multi-graph programs to the
double-buffered form (the MPI_Isend/Irecv analogue): the scan carry
holds the *pre-exchanged* context for the current timestep, and each
step issues the next timestep's exchange immediately after producing its
payload — ahead of the next kernel body — so XLA's async collectives may
run while compute proceeds.  The final timestep runs outside the scan
(its payload needs no exchange), so both forms issue exactly H
exchanges, and the exchanged values are identical — conformance is
bit-exact either way.

``comm="onesided"`` drops the rendezvous entirely (the NVSHMEM-style
put/signal idiom): the scan carry holds the plan's receive buffers and
signal counters (``CommPlan.onesided_state``), each step's producers
push their dependency rows and raise the consumer's flag
(``onesided_push``), and consumers assemble their context through the
masked ``signal_wait_until`` (``onesided_wait``) instead of joining a
collective.  Composes with ``comm_overlap`` (the wait for step t+1 is
issued right after step t's push, ahead of the next kernel body) and
with the combined multi-graph program; bit-exact with every other mode.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..compat import pcast, shard_map
from ..core.graph import TaskGraph
from ..dist import collectives as CC
from ..launch.mesh import make_mesh, rank_mesh
from . import body
from .base import Backend, register_backend

AXIS = "cols"


class PlannedSPMDBackend(Backend):
    """Columns blocked over one mesh axis; movement per a ``CommPlan``.

    Ragged widths are handled by the plan's dead-column padding, so any
    graph width runs on any rank count (including width < ndev).  The
    ranks are ``mesh``'s devices; without one, see ``launch.mesh.rank_mesh``.
    """

    axis = AXIS
    prefer_ring = False

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
        progs = [self._prepare_one(g) for g in graphs]

        def runner() -> List[np.ndarray]:
            outs = [p() for p in progs]
            return [np.asarray(o) for o in outs]

        return runner

    def _compile_one(self, graph: TaskGraph):
        plan = self.plan(graph)
        local, Pels = plan.local, graph.payload_elems
        lmats_j = jnp.asarray(plan.local_mats)
        iters_j = jnp.asarray(plan.iters)
        dynamic = local == 1  # true per-rank loops can stop early

        def rank_program(lmats_l, iters_l):
            """Runs on one rank: lmats_l (H, local, ctx), iters_l (H, local)."""
            cols = plan.local_cols()
            payload0 = jnp.zeros((local, Pels), jnp.float32)
            # the carry becomes device-varying after the first exchange;
            # mark it so from the start (shard_map vma typing)
            payload0 = pcast(payload0, (self.axis,), to="varying")
            ts = jnp.arange(graph.height, dtype=jnp.uint32)

            if plan.mode == "onesided":
                recv0, sig0 = plan.onesided_state(Pels)
                recv0 = pcast(recv0, (self.axis,), to="varying")
                sig0 = pcast(sig0, (self.axis,), to="varying")

                if plan.comm_overlap:
                    # put/signal double buffering: step t pushes its
                    # payload, then immediately issues step t+1's masked
                    # wait — ahead of the next kernel body
                    def step(carry, xs):
                        ctx, recv, sig = carry
                        t, mat_t, it_t = xs
                        new = body.timestep(graph, t, ctx, mat_t, it_t,
                                            cols=cols, dynamic=dynamic)
                        recv, sig = plan.onesided_push(new, recv, sig)
                        ctx = plan.onesided_wait(recv, sig, t + 1, new)
                        return (ctx, recv, sig), None

                    ctx0 = plan.onesided_wait(recv0, sig0, 0, payload0)
                    (ctx, _, _), _ = jax.lax.scan(
                        step, (ctx0, recv0, sig0),
                        (ts[:-1], lmats_l[:-1], iters_l[:-1]))
                    return body.timestep(graph, ts[-1], ctx, lmats_l[-1],
                                         iters_l[-1], cols=cols,
                                         dynamic=dynamic)

                def step(carry, xs):
                    payload, recv, sig = carry
                    t, mat_t, it_t = xs
                    ctx = plan.onesided_wait(recv, sig, t, payload)
                    new = body.timestep(graph, t, ctx, mat_t, it_t,
                                        cols=cols, dynamic=dynamic)
                    recv, sig = plan.onesided_push(new, recv, sig)
                    return (new, recv, sig), None

                (final, _, _), _ = jax.lax.scan(
                    step, (payload0, recv0, sig0), (ts, lmats_l, iters_l))
                return final

            if plan.comm_overlap:
                # double-buffered: the carry holds this step's already-
                # exchanged context; each step issues the *next* step's
                # exchange ahead of the next kernel body.  The last
                # timestep runs outside the scan — its payload needs no
                # further exchange, so the program issues exactly H
                # exchanges, the same count as the blocking form
                def step(ctx_payload, xs):
                    t, mat_t, it_t = xs
                    new = body.timestep(graph, t, ctx_payload, mat_t, it_t,
                                        cols=cols, dynamic=dynamic)
                    return plan.exchange(new), None

                ctx, _ = jax.lax.scan(
                    step, plan.exchange(payload0),
                    (ts[:-1], lmats_l[:-1], iters_l[:-1]))
                return body.timestep(graph, ts[-1], ctx, lmats_l[-1],
                                     iters_l[-1], cols=cols, dynamic=dynamic)

            def step(payload, xs):
                t, mat_t, it_t = xs
                ctx_payload = plan.exchange(payload)
                new = body.timestep(graph, t, ctx_payload, mat_t, it_t,
                                    cols=cols, dynamic=dynamic)
                return new, None

            final, _ = jax.lax.scan(step, payload0, (ts, lmats_l, iters_l))
            return final

        shmapped = shard_map(
            rank_program,
            mesh=self.mesh,
            in_specs=(P(None, self.axis, None), P(None, self.axis)),
            out_specs=P(self.axis, None),
        )
        fn = jax.jit(shmapped)
        compiled = fn.lower(lmats_j, iters_j).compile()
        return compiled, plan, lmats_j, iters_j

    def _prepare_one(self, graph: TaskGraph):
        compiled, plan, lmats_j, iters_j = self._compile_one(graph)

        def run_one():
            out = jax.block_until_ready(compiled(lmats_j, iters_j))
            return plan.trim(out)

        return run_one

    def _compile_combined(self, graphs: Sequence[TaskGraph]):
        """One shard_map program interleaving every graph's wavefront.

        Each scan step exchanges and executes timestep ``t`` of *all*
        graphs, so XLA may overlap one graph's ppermute/all_gather with
        another's kernels — the rank-parallel form of task parallelism.
        Requires a common height (the shared clock); None otherwise.
        """
        if len(graphs) < 2 or len({g.height for g in graphs}) != 1:
            return None
        plans = [self.plan(g) for g in graphs]
        height = graphs[0].height
        dynamics = [p.local == 1 for p in plans]
        lmats = tuple(jnp.asarray(p.local_mats) for p in plans)
        iters = tuple(jnp.asarray(p.iters) for p in plans)

        def rank_program(lmats_l, iters_l):
            colss = tuple(p.local_cols() for p in plans)
            payloads = tuple(
                pcast(jnp.zeros((p.local, g.payload_elems), jnp.float32),
                      (self.axis,), to="varying")
                for p, g in zip(plans, graphs))
            ts = jnp.arange(height, dtype=jnp.uint32)

            if self.comm == "onesided":
                # every graph's (recv, sig) rides the shared carry; each
                # step pushes/waits all graphs, so one graph's puts may
                # overlap another's kernels like the collective forms
                states = tuple(
                    tuple(pcast(s, (self.axis,), to="varying")
                          for s in p.onesided_state(g.payload_elems))
                    for p, g in zip(plans, graphs))

                if self.comm_overlap:
                    def step(carry, xs):
                        t, mats_t, its_t = xs
                        out = []
                        for g, p, (ctx, recv, sig), m, it, cols, dyn in zip(
                                graphs, plans, carry, mats_t, its_t,
                                colss, dynamics):
                            new = body.timestep(g, t, ctx, m, it,
                                                cols=cols, dynamic=dyn)
                            recv, sig = p.onesided_push(new, recv, sig)
                            ctx = p.onesided_wait(recv, sig, t + 1, new)
                            out.append((ctx, recv, sig))
                        return tuple(out), None

                    init = tuple(
                        (p.onesided_wait(recv, sig, 0, c), recv, sig)
                        for p, c, (recv, sig) in zip(plans, payloads, states))
                    carry, _ = jax.lax.scan(
                        step, init,
                        (ts[:-1], tuple(m[:-1] for m in lmats_l),
                         tuple(i[:-1] for i in iters_l)))
                    return tuple(
                        body.timestep(g, ts[-1], ctx, m[-1], it[-1],
                                      cols=cols, dynamic=dyn)
                        for g, (ctx, _, _), m, it, cols, dyn in zip(
                            graphs, carry, lmats_l, iters_l, colss,
                            dynamics))

                def step(carry, xs):
                    t, mats_t, its_t = xs
                    out = []
                    for g, p, (payload, recv, sig), m, it, cols, dyn in zip(
                            graphs, plans, carry, mats_t, its_t,
                            colss, dynamics):
                        ctx = p.onesided_wait(recv, sig, t, payload)
                        new = body.timestep(g, t, ctx, m, it,
                                            cols=cols, dynamic=dyn)
                        recv, sig = p.onesided_push(new, recv, sig)
                        out.append((new, recv, sig))
                    return tuple(out), None

                init = tuple((c,) + s for c, s in zip(payloads, states))
                carry, _ = jax.lax.scan(step, init, (ts, lmats_l, iters_l))
                return tuple(payload for payload, _, _ in carry)

            if self.comm_overlap:
                # as in _compile_one: the last tick runs outside the scan
                # so every pipeline issues exactly H exchanges
                def step(ctxs, xs):
                    t, mats_t, its_t = xs
                    new = tuple(
                        body.timestep(g, t, ctx, m, it,
                                      cols=cols, dynamic=dyn)
                        for g, ctx, m, it, cols, dyn in zip(
                            graphs, ctxs, mats_t, its_t, colss, dynamics))
                    return tuple(p.exchange(n)
                                 for p, n in zip(plans, new)), None

                ctxs, _ = jax.lax.scan(
                    step,
                    tuple(p.exchange(c) for p, c in zip(plans, payloads)),
                    (ts[:-1], tuple(m[:-1] for m in lmats_l),
                     tuple(i[:-1] for i in iters_l)))
                return tuple(
                    body.timestep(g, ts[-1], ctx, m[-1], it[-1],
                                  cols=cols, dynamic=dyn)
                    for g, ctx, m, it, cols, dyn in zip(
                        graphs, ctxs, lmats_l, iters_l, colss, dynamics))

            def step(carry, xs):
                t, mats_t, its_t = xs
                new = tuple(
                    body.timestep(g, t, p.exchange(c), m, it,
                                  cols=cols, dynamic=dyn)
                    for g, p, c, m, it, cols, dyn in zip(
                        graphs, plans, carry, mats_t, its_t, colss, dynamics))
                return new, None

            final, _ = jax.lax.scan(step, payloads, (ts, lmats_l, iters_l))
            return final

        shmapped = shard_map(
            rank_program,
            mesh=self.mesh,
            in_specs=(tuple(P(None, self.axis, None) for _ in plans),
                      tuple(P(None, self.axis) for _ in plans)),
            out_specs=tuple(P(self.axis, None) for _ in plans),
        )
        compiled = jax.jit(shmapped).lower(lmats, iters).compile()
        return compiled, plans, lmats, iters

    def prepare_many(self, graphs: Sequence[TaskGraph]):
        graphs = list(graphs)
        built = self._compile_combined(graphs)
        if built is None:
            return self.prepare(graphs)
        compiled, plans, lmats, iters = built

        def runner() -> List[np.ndarray]:
            outs = jax.block_until_ready(compiled(lmats, iters))
            return [np.asarray(p.trim(o)) for p, o in zip(plans, outs)]

        return runner

    def lowered_hlo(self, graphs: Sequence[TaskGraph]) -> List[str]:
        graphs = list(graphs)
        built = self._compile_combined(graphs)
        if built is not None:
            return [built[0].as_text()]
        return [self._compile_one(g)[0].as_text() for g in graphs]


@register_backend("shardmap-csp")
class CSPBackend(PlannedSPMDBackend):
    paradigm = "explicit SPMD message passing (MPI CSP analogue)"
