"""Unified communication planning for SPMD backends (the comm-plan layer).

Both rank-parallel backends (``shardmap-csp``, ``shardmap-pipeline``) and
the distributed training stack move dependency payloads between device
ranks each timestep.  This module lifts that planning out of the backends
into one reusable object, ``CommPlan``:

* **analysis** — ``dependency_reach``/``directional_reach`` vectorize the
  dependence-offset scan over ``TaskGraph.dependence_matrices()`` (one
  ``np.nonzero`` over the whole stack instead of a Python loop per
  timestep) and short-circuit to a single timestep slice for
  time-invariant graphs;
* **placement** — columns are blocked over ``ndev`` ranks, padding ragged
  widths up to the next multiple with *dead columns* (zero dependence
  rows, zero iterations) so any width runs on any rank count — the
  paper's MPI implementation handles ragged columns the same way;
* **movement** — four modes, the first three selected automatically from
  the reach:

  ====================  =====================================================
  ``ring``              one-directional ``ppermute`` toward higher ranks —
                        the pipeline stage-to-stage activation transfer
                        (deps reach left only, e.g. sweep graphs)
  ``halo``              bidirectional nearest-neighbour ``ppermute``
                        exchange (stencil/nearest reach fits in a halo)
  ``allgather``         full payload-row gather — the MPI_Allgather
                        fallback for wide patterns (fft/spread/random)
  ``a2a``               per-pair ``all_to_all``: each rank sends every other
                        rank exactly the payload rows that rank's columns
                        depend on (MPI_Alltoallv analogue); send/recv counts
                        form a permutation — tokens are conserved
  ``onesided``          NVSHMEM-style put/signal: producers *push* their
                        dependency rows straight into per-consumer receive
                        buffers and raise a signal flag; consumers spin on a
                        ``signal_wait_until`` mask instead of joining a
                        rendezvous.  Same per-pair slot layout as ``a2a``,
                        but the receive buffers and signal counters persist
                        across timesteps (scan state), so there is no
                        collective barrier per step — the portable emulation
                        moves each packet with a point-to-point ``ppermute``
                        and carries the signal with the payload
  ====================  =====================================================

``CommPlan.exchange`` executes the planned movement *inside* ``shard_map``;
``CommPlan.local_mats`` are the dependence matrices re-indexed into each
rank's context window (``[left halo | local block | right halo]`` for the
ppermute modes, ``[recv buffers | local block]`` for ``a2a``/``onesided``).
For ``onesided`` the stateful form is primary: ``onesided_state`` builds
the (receive buffers, signals) pair the executing scan carries,
``onesided_push`` is the producer's put+signal, ``onesided_wait`` the
consumer's masked ``signal_wait_until`` + context assembly.

This module also owns the *dynamic* token all-to-all used by MoE expert
parallelism (``TokenA2APlan``): the same dispatch planning — capacity
sizing, slotting, per-destination buffers, forward/reverse ``all_to_all``
— with the destination of each row decided at runtime by the router
instead of statically by the dependence matrices.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import TaskGraph

MODES = ("auto", "ring", "halo", "allgather", "a2a", "onesided")


def _dep_offsets(graph: TaskGraph) -> np.ndarray:
    """All distinct dependence offsets ``j - i`` across the graph.

    Vectorized: one ``np.nonzero`` over the stacked matrices; graphs whose
    dependence relation is time-invariant are analyzed from a single
    timestep slice instead of the full (H, W, W) stack.
    """
    if graph.height <= 1:
        return np.empty((0,), np.int64)
    if graph.is_time_invariant():
        mats = graph.dependence_matrix(1)[None]
    else:
        mats = graph.dependence_matrices()[1:]
    _, i, j = np.nonzero(mats)
    return np.unique(j.astype(np.int64) - i.astype(np.int64))


def directional_reach(graph: TaskGraph) -> Tuple[int, int]:
    """(left, right): how far deps reach toward lower / higher columns."""
    offs = _dep_offsets(graph)
    if offs.size == 0:
        return 0, 0
    return int(max(-offs.min(), 0)), int(max(offs.max(), 0))


def dependency_reach(graph: TaskGraph) -> int:
    """max |j - i| over all deps — the halo width an MPI rank would post."""
    left, right = directional_reach(graph)
    return max(left, right)


# eq=False: ndarray fields would make the generated __eq__/__hash__ raise
@dataclasses.dataclass(frozen=True, eq=False)
class CommPlan:
    """How one graph's payloads are laid out and moved over ``ndev`` ranks.

    ``local_mats``/``iters`` are padded to ``padded_width`` columns; dead
    columns (>= ``width``) have empty dependence rows and zero iterations,
    and are sliced away by ``trim``.
    """

    mode: str            # "ring" | "halo" | "allgather" | "a2a" | "onesided"
    axis: str            # mesh axis name the ranks live on
    ndev: int
    width: int           # real graph width
    padded_width: int    # next multiple of ndev
    local: int           # columns per rank
    halo: int            # exchange width (0 => no communication)
    local_mats: np.ndarray   # (H, padded_width, ctx) uint8
    iters: np.ndarray        # (H, padded_width) int32
    # double-buffered communication: the executing program issues timestep
    # t+1's exchange right after timestep t's payload is produced (ahead
    # of t+1's kernel body), so XLA's async collectives may overlap with
    # compute.  Pure program-shape flag: ``exchange`` itself is identical.
    comm_overlap: bool = False
    # a2a/onesided modes: [src, dst] row counts and padded send-row indices
    send_counts: Optional[np.ndarray] = None   # (ndev, ndev) int64
    a2a_cap: int = 0                           # rows per (src, dst) buffer
    a2a_send_idx: Optional[np.ndarray] = None  # (ndev, ndev, cap) int32

    @property
    def ragged(self) -> bool:
        return self.padded_width != self.width

    @property
    def recv_counts(self) -> Optional[np.ndarray]:
        """[dst, src] rows received — the transpose of ``send_counts``:
        every row sent is received exactly once (token conservation)."""
        return None if self.send_counts is None else self.send_counts.T

    @property
    def context_width(self) -> int:
        """Columns of t-1 payload visible to each rank after exchange."""
        return self.local_mats.shape[-1]

    def local_cols(self):
        """Global column ids of the calling rank (inside ``shard_map``)."""
        rank = jax.lax.axis_index(self.axis)
        return rank * self.local + jnp.arange(self.local)

    def exchange(self, payload):
        """Move t-1 payloads into this rank's context (inside ``shard_map``).

        payload: (local, P) f32 — the rank's own previous-timestep rows.
        Returns (context_width, P) rows ordered to match ``local_mats``.
        """
        with jax.named_scope("exchange"):
            if self.mode == "allgather":
                return jax.lax.all_gather(payload, self.axis, tiled=True)
            if self.mode == "onesided":
                # stateless fallback (one-shot put + immediate wait); the
                # executing backends carry (recv, sig) across steps instead
                recv, sig = self.onesided_state(payload.shape[-1],
                                                payload.dtype)
                recv, sig = self.onesided_push(payload, recv, sig)
                return self.onesided_wait(recv, sig, 1, payload)
            if self.mode == "a2a":
                if self.a2a_cap == 0:
                    # no remote deps: the context is the local block
                    return payload
                rank = jax.lax.axis_index(self.axis)
                idx = jnp.take(jnp.asarray(self.a2a_send_idx), rank, axis=0)
                send = jnp.take(payload, idx, axis=0)      # (ndev, cap, P)
                recv = jax.lax.all_to_all(send, self.axis, 0, 0)
                return jnp.concatenate(
                    [recv.reshape(self.ndev * self.a2a_cap, -1), payload])
            if self.halo == 0:
                return payload
            h, P = self.halo, payload.shape[-1]
            zeros = jnp.zeros((h, P), payload.dtype)
            fwd = [(r, r + 1) for r in range(self.ndev - 1)]
            from_left = (jax.lax.ppermute(payload[-h:], self.axis, fwd)
                         if fwd else zeros)
            if self.mode == "ring":
                return jnp.concatenate([from_left, payload])
            bwd = [(r, r - 1) for r in range(1, self.ndev)]
            from_right = (jax.lax.ppermute(payload[:h], self.axis, bwd)
                          if bwd else zeros)
            return jnp.concatenate([from_left, payload, from_right])

    def trim(self, gathered):
        """Drop dead padding columns from a (padded_width, ...) output."""
        return gathered[: self.width]

    # ------------------------------------------ onesided put/signal mode
    @functools.cached_property
    def _onesided_offsets(self) -> List[Tuple[int, np.ndarray, np.ndarray]]:
        """Static transport schedule: one entry per *active* ring offset.

        ``(offset, idx_table, flag_table)``: rank ``r`` puts the payload
        rows ``idx_table[r]`` to rank ``(r + offset) % ndev`` and raises
        the consumer's signal iff ``flag_table[r]`` (the pair is live).
        Every rank executes every offset's put — the SPMD-uniform
        structure one-sided hardware paths (remote DMA) require — and
        dead pairs deliver masked garbage no ``local_mats`` entry reads.
        """
        assert self.mode == "onesided" and self.send_counts is not None
        out: List[Tuple[int, np.ndarray, np.ndarray]] = []
        for off in range(1, self.ndev):
            dsts = (np.arange(self.ndev) + off) % self.ndev
            live = self.send_counts[np.arange(self.ndev), dsts] > 0
            if not live.any():
                continue
            idx = self.a2a_send_idx[np.arange(self.ndev), dsts]  # (ndev, cap)
            out.append((off, idx.astype(np.int32),
                        live.astype(np.float32)))
        return out

    def onesided_state(self, payload_elems: int, dtype=jnp.float32):
        """Fresh (recv buffers, signal counters) for the executing scan.

        ``recv[s]`` is the ``a2a_cap``-row buffer rank ``s`` puts into on
        this rank; ``sig[s]`` counts the epochs rank ``s`` has signalled.
        """
        recv = jnp.zeros((self.ndev, self.a2a_cap, payload_elems), dtype)
        sig = jnp.zeros((self.ndev,), jnp.int32)
        return recv, sig

    def onesided_push(self, payload, recv, sig):
        """The producer side: put dependency rows into each consumer's
        receive buffer and raise its signal (``put`` + ``putmem_signal``).

        The portable emulation moves each (rows, flag) packet with one
        point-to-point ``ppermute`` per active ring offset — the flag
        travels *with* the payload, so the signal is genuinely raised by
        the producer, not inferred by the consumer.  Slot writes use
        ``.at[...].set(mode="drop")`` like the token-dispatch path.
        """
        with jax.named_scope("exchange"):
            if self.a2a_cap == 0:
                return recv, sig
            rank = jax.lax.axis_index(self.axis)
            P = payload.shape[-1]
            for off, idx_tab, flag_tab in self._onesided_offsets:
                idx = jnp.take(jnp.asarray(idx_tab), rank, axis=0)   # (cap,)
                block = jnp.take(payload, idx, axis=0)               # (cap, P)
                flag = jnp.take(jnp.asarray(flag_tab), rank)
                packet = jnp.concatenate(
                    [block, jnp.full((1, P), flag, block.dtype)])
                perm = [(r, (r + off) % self.ndev) for r in range(self.ndev)]
                got = jax.lax.ppermute(packet, self.axis, perm)
                src = jax.lax.rem(rank - off + self.ndev, self.ndev)
                recv = recv.at[src].set(got[:-1], mode="drop")
                sig = sig.at[src].add(got[-1, 0].astype(jnp.int32),
                                      mode="drop")
            return recv, sig

    def onesided_wait(self, recv, sig, t, payload):
        """The consumer side: ``signal_wait_until`` + context assembly.

        Receive slots whose producer has not signalled epoch ``t`` yet
        read as zeros (the masked wait) — which is also what makes the
        mode bit-exact with blocking: dead pairs and the t=0 epoch are
        masked instead of synchronized away.
        """
        with jax.named_scope("exchange"):
            if self.a2a_cap == 0:
                return payload
            ready = sig >= jnp.asarray(t).astype(sig.dtype)
            slots = jnp.where(ready[:, None, None], recv, jnp.zeros_like(recv))
            return jnp.concatenate(
                [slots.reshape(self.ndev * self.a2a_cap, -1), payload])


def _padded_static_inputs(graph: TaskGraph, padded: int):
    """Dep matrices (H, padded, padded) u8 + iteration counts (H, padded)."""
    from ..backends import body  # local import: backends import this module

    mats, iters = body.graph_static_inputs(graph)
    W = graph.width
    if padded == W:
        return mats, iters
    H = graph.height
    pm = np.zeros((H, padded, padded), np.uint8)
    pm[:, :W, :W] = mats
    pi = np.zeros((H, padded), np.int32)  # dead columns: no work
    pi[:, :W] = iters
    return pm, pi


def plan_comm(
    graph: TaskGraph,
    ndev: int,
    axis: str,
    comm: str = "auto",
    prefer_ring: bool = False,
    comm_overlap: bool = False,
) -> CommPlan:
    """Build the communication plan for ``graph`` over ``ndev`` ranks.

    ``comm`` forces a mode; ``auto`` picks the cheapest legal one (never
    ``a2a`` or ``onesided``, which must be requested — per-pair buffers
    only beat the allgather when the dependence relation is sparse, and
    put/signal trades rendezvous latency for buffer space).  With
    ``prefer_ring`` (pipeline backends), graphs whose deps reach only
    toward lower columns use the one-directional ring instead of the
    bidirectional halo.  ``comm_overlap`` asks the executing backend for
    the double-buffered program shape (next step's exchange issued ahead
    of this step's kernel body); results are bit-identical either way.
    """
    if comm not in MODES:
        raise ValueError(f"unknown comm mode {comm!r}; known: {MODES}")
    if ndev < 1:
        raise ValueError(f"need at least one rank, got {ndev}")
    W, H = graph.width, graph.height
    padded = -(-W // ndev) * ndev
    local = padded // ndev
    left, right = directional_reach(graph)
    reach = max(left, right)

    if comm == "auto":
        if reach > local:
            mode = "allgather"
        elif prefer_ring and right == 0:
            mode = "ring"
        else:
            mode = "halo"
    else:
        mode = comm
        if mode == "ring" and right > 0:
            raise ValueError(
                f"ring comm needs left-only deps, but reach is "
                f"(left={left}, right={right})")
        if mode in ("ring", "halo") and reach > local:
            raise ValueError(
                f"{mode} comm cannot cover reach {reach} with "
                f"{local} columns per rank; use allgather")

    mats, iters = _padded_static_inputs(graph, padded)
    if mode in ("a2a", "onesided"):
        plan = _plan_a2a(graph, ndev, axis, mats, iters, padded, local,
                         mode=mode)
        return dataclasses.replace(plan, comm_overlap=comm_overlap) \
            if comm_overlap else plan
    if mode == "allgather":
        halo = 0
        lmats = mats  # context is the full gathered (padded) width
    else:
        halo = min(reach if mode == "halo" else left, local)
        lhalo, rhalo = halo, (halo if mode == "halo" else 0)
        ctx = lhalo + local + rhalo
        lmats = np.zeros((H, padded, ctx), np.uint8)
        t_idx, i_idx, j_idx = np.nonzero(mats)
        # re-index dep columns into [left halo | local block | right halo]
        lj = j_idx - ((i_idx // local) * local - lhalo)
        assert ((0 <= lj) & (lj < ctx)).all(), (mode, halo, local)
        lmats[t_idx, i_idx, lj] = 1

    return CommPlan(
        mode=mode, axis=axis, ndev=ndev, width=W, padded_width=padded,
        local=local, halo=halo, local_mats=lmats, iters=iters,
        comm_overlap=comm_overlap,
    )


def _plan_a2a(graph: TaskGraph, ndev: int, axis: str,
              mats: np.ndarray, iters: np.ndarray,
              padded: int, local: int, mode: str = "a2a") -> CommPlan:
    """Per-pair dispatch plan: rank ``src`` sends rank ``dst`` exactly the
    payload columns ``dst``'s tasks read from ``src``'s block (union over
    timesteps, one plan reused per step like the halo modes).  Buffers are
    padded to the max pair count; unused send slots carry an arbitrary
    local row that no ``local_mats`` entry references.

    ``onesided`` shares this slot layout byte-for-byte — only the
    transport differs (producer puts + signals instead of the collective
    ``all_to_all``), so conformance between the modes is structural.
    """
    H = graph.height
    t_idx, i_idx, j_idx = np.nonzero(mats)
    src, dst = j_idx // local, i_idx // local
    remote = src != dst
    # unique (src, dst, j) triples, lexically sorted — fixes the slot order
    triples = np.unique(
        np.stack([src[remote], dst[remote], j_idx[remote]], axis=1), axis=0)
    send_counts = np.zeros((ndev, ndev), np.int64)
    for s, d, _ in triples:
        send_counts[s, d] += 1
    cap = int(send_counts.max()) if triples.size else 0
    send_idx = np.zeros((ndev, ndev, cap), np.int32)
    # context offset of remote column j for its consumer rank:
    # [recv buffers (ndev * cap, src-major) | local block]
    col_off = {}
    slot = np.zeros((ndev, ndev), np.int64)
    for s, d, j in triples:
        k = slot[s, d]
        slot[s, d] += 1
        send_idx[s, d, k] = j - s * local
        col_off[(d, j)] = s * cap + k
    ctx = ndev * cap + local
    lmats = np.zeros((H, padded, ctx), np.uint8)
    for t, i, j in zip(t_idx, i_idx, j_idx):
        r = i // local
        off = (ndev * cap + (j - r * local)) if j // local == r \
            else col_off[(r, j)]
        lmats[t, i, off] = 1
    return CommPlan(
        mode=mode, axis=axis, ndev=ndev, width=graph.width,
        padded_width=padded, local=local, halo=0, local_mats=lmats,
        iters=iters, send_counts=send_counts, a2a_cap=cap,
        a2a_send_idx=send_idx,
    )


# ---------------------------------------------- dynamic token all-to-all
def dispatch_capacity(sends: int, ndev: int, factor: float) -> int:
    """Rows per destination-rank buffer for ``sends`` routed items.

    ``factor`` is the MoE capacity factor; the result is padded to a
    multiple of 8 (TPU sublane) with a floor of 8 so tiny shards still
    form a legal tile.  Sends beyond a destination's capacity are dropped
    deterministically in send order (``TokenA2APlan.route``).
    """
    return max(8, int(math.ceil(factor * sends / ndev / 8.0) * 8))


@dataclasses.dataclass(frozen=True)
class TokenA2APlan:
    """Routing-dependent all-to-all over ``axis`` (MoE dispatch/combine).

    The static part — ``cap`` rows per destination, slot assignment by
    arrival order, forward/reverse ``all_to_all`` — is planned here; the
    per-row destinations arrive at runtime from the router.  All methods
    run *inside* ``shard_map``.  Volume per rank per direction:
    ``ndev * cap`` rows — the quantity the SP-aware MoE cuts by sharding
    tokens over the ``model`` axis before planning.
    """

    axis: str
    ndev: int
    cap: int

    def route(self, dest):
        """dest (M,) int32 -> (slot, keep).

        ``slot`` is each row's arrival index among same-destination rows
        (deterministic in send order — the paper-style capacity drop);
        rows with ``slot >= cap`` are parked on the overflow slot ``cap``
        and masked by ``keep``.
        """
        onehot = jax.nn.one_hot(dest, self.ndev, dtype=jnp.int32)
        slot = jnp.cumsum(onehot, axis=0) - onehot
        slot = (slot * onehot).sum(-1)
        keep = slot < self.cap
        return jnp.where(keep, slot, self.cap), keep

    def dispatch(self, dest, slot, rows, fill=0):
        """Exchange rows (M, ...) toward their destination ranks.

        Returns this rank's received rows, flattened to ``(ndev * cap,
        ...)``: row ``s * cap + k`` is the k-th row rank ``s`` sent here.
        Empty/overflow slots hold ``fill``.
        """
        shape = (self.ndev, self.cap + 1) + rows.shape[1:]
        buf = jnp.full(shape, fill, rows.dtype)
        buf = buf.at[dest, slot].set(rows, mode="drop")[:, : self.cap]
        recv = jax.lax.all_to_all(buf, self.axis, 0, 0)
        return recv.reshape((self.ndev * self.cap,) + rows.shape[1:])

    def combine(self, out_rows, dest, slot):
        """Reverse exchange: out_rows ``(ndev * cap, ...)`` keyed like
        ``dispatch``'s result travel back to the senders; returns one row
        per original send (M, ...).  Dropped sends read the overflow slot
        — mask the result with ``keep`` from ``route``.
        """
        back = jax.lax.all_to_all(
            out_rows.reshape((self.ndev, self.cap) + out_rows.shape[1:]),
            self.axis, 0, 0)
        return back[dest, jnp.clip(slot, 0, self.cap - 1)]
