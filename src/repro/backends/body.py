"""Shared JAX task body used by every backend (the O(m+n) trick).

All backends execute the *same* width-vectorized task body; they differ only
in how timesteps are scheduled and how dependency payloads move.  This
mirrors the paper's core API: the task body and kernels are provided
centrally so that backend comparisons are apples-to-apples (paper §II).

Numerical contract (must match core.kernel_ref bitwise for elementwise
kernels): the kernel state is seeded with ``start + acc * 2**-46`` where
``acc < 2**20`` — this rounds to exactly ``start`` in float32 (the increment
is below half an ulp of every start value used) but blocks XLA constant
folding, so the kernel loop is always executed at run time.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import CHECKSUM_MOD, TaskGraph
from ..core.kernel_spec import KernelSpec
from ..kernels import bodies

_FOLD_BLOCK = bodies.FOLD_BLOCK  # see module docstring


def checksum_vec(t, cols):
    """uint32-wrapping checksum; matches TaskGraph.checksum exactly."""
    t = jnp.asarray(t, jnp.uint32)
    cols = jnp.asarray(cols, jnp.uint32)
    k1 = jnp.uint32(2654435761)
    k2 = jnp.uint32(40503)
    return ((t * k1 + cols * k2) % jnp.uint32(CHECKSUM_MOD)).astype(jnp.uint32)


def combine_acc(dep_matrix, prev_combined):
    """acc_i = sum_j M[i,j] * combined_j  (mod 2^20), exact uint32 math."""
    m = dep_matrix.astype(jnp.uint32)  # (W, W)
    acc = (m * prev_combined[None, :].astype(jnp.uint32)).sum(axis=1)
    return (acc % jnp.uint32(CHECKSUM_MOD)).astype(jnp.uint32)


def run_kernel_vec(kernel: KernelSpec, iters_per_col, acc, max_iters: int,
                   dynamic: bool = False):
    """Vectorized kernel over width; returns (W,) f32 results.

    Thin rank adapter over ``kernels.bodies.run_kernel_columns`` — the
    megakernel backend and the standalone Pallas kernels call the same
    step functions, so every execution layer shares one code path (the
    reshapes here are exact; results stay bitwise identical).
    """
    seed = acc.astype(jnp.float32) * jnp.float32(_FOLD_BLOCK)
    out = bodies.run_kernel_columns(kernel, iters_per_col[:, None],
                                    seed[:, None], max_iters,
                                    dynamic=dynamic)
    return out[:, 0]


def make_payload(t, cols, base, combined, result, payload_elems: int):
    """Assemble the (ncols, P) payload rows for global column ids ``cols``."""
    n = cols.shape[0]
    tt = jnp.broadcast_to(jnp.asarray(t, jnp.float32), (n,))
    head = jnp.stack(
        [tt, cols.astype(jnp.float32), base.astype(jnp.float32),
         combined.astype(jnp.float32), result],
        axis=1,
    )
    if payload_elems > 5:
        ballast = jnp.broadcast_to(result[:, None], (n, payload_elems - 5))
        return jnp.concatenate([head, ballast], axis=1)
    return head


def timestep(graph: TaskGraph, t, prev_payload, dep_matrix, iters_per_col,
             cols=None, dynamic: bool = False):
    """Execute one timestep of ``graph``, vectorized over a column block.

    prev_payload: (W_ctx, P) f32 from t-1 — the *context* columns this block
                  can read (full width for single-device backends; local
                  block + halo/gathered columns for CSP shards).
    dep_matrix:   (n, W_ctx) uint8 — rows select deps within the context.
    iters_per_col:(n,) int32 — per-task durations (imbalance-aware).
    cols:         (n,) global column ids (defaults to arange(W_ctx)).
    Returns the new (n, P) payload block.

    Its parts carry the named scopes ``combine``, ``checksum``, ``kernel``
    and ``payload``: they name the compiled ops (``op_name`` metadata), so
    a device trace tells them apart.  They change no computation.
    """
    if cols is None:
        cols = jnp.arange(graph.width)
    with jax.named_scope("combine"):
        prev_combined = prev_payload[:, 3].astype(jnp.uint32)
        acc = combine_acc(dep_matrix, prev_combined)
    with jax.named_scope("checksum"):
        base = checksum_vec(t, cols)
        combined = (base + acc) % jnp.uint32(CHECKSUM_MOD)
    with jax.named_scope("kernel"):
        result = run_kernel_vec(graph.kernel, iters_per_col, acc,
                                graph.kernel.iterations, dynamic=dynamic)
    with jax.named_scope("payload"):
        return make_payload(t, cols, base, combined, result,
                            graph.payload_elems)


def graph_static_inputs(graph: TaskGraph) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side constants: dep matrices (H,W,W) u8 and iteration counts (H,W) i32."""
    mats = graph.dependence_matrices().astype(np.uint8)
    iters = np.array(
        [[graph.task_iterations(t, i) for i in range(graph.width)]
         for t in range(graph.height)],
        dtype=np.int32,
    )
    return mats, iters


def stackable(graphs: Sequence[TaskGraph]) -> bool:
    """Can these graphs share one vectorized program with a graph axis?

    The task body closes over shape (width/payload) and kernel spec; the
    dependence matrices and iteration counts are data.  So graphs stack iff
    those static parts agree — patterns may differ freely.
    """
    if len(graphs) < 2:
        return False
    g0 = graphs[0]
    return all(
        g.width == g0.width
        and g.height == g0.height
        and g.output_bytes == g0.output_bytes
        and g.kernel == g0.kernel
        for g in graphs[1:]
    )


def stacked_static_inputs(
    graphs: Sequence[TaskGraph],
) -> Tuple[np.ndarray, np.ndarray]:
    """Static inputs with a leading graph axis: (G,H,W,W) u8, (G,H,W) i32."""
    per_graph = [graph_static_inputs(g) for g in graphs]
    mats = np.stack([m for m, _ in per_graph])
    iters = np.stack([i for _, i in per_graph])
    return mats, iters
