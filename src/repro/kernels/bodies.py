"""Composable in-kernel Task Bench bodies shared by every execution layer.

The three Task Bench kernel inner loops (compute / compute_mxu / memory)
written as composable step functions plus one masked iteration loop, in a
form legal both inside jitted XLA programs (``backends.body``) and inside
other Pallas kernels (``backends.megakernel``, ``kernels.compute``,
``kernels.memory``).  One code path -> bit-exact conformance everywhere:
the jitted backends and the fused megakernel literally execute these same
traced operations.

Mosaic (Pallas TPU) legality constraints honored here:

* column-vector ``(W, 1)`` working shapes — never rank-1 intermediates
  (Mosaic cannot lower 1-D vector ops on this toolchain)
* no uint32 arithmetic (checksums are int32-exact: values < 2^20)
* no captured array constants — the MXU weight is an explicit argument so
  kernels can pass it in as a ref instead of baking in a (128,128) const
* no ``dynamic_slice`` on a value (Mosaic has no lowering for it) — Pallas
  callers give the memory kernel a ``(nwin, W, span)`` VMEM ref and it
  walks the windows by indexing the ref's leading axis
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.kernel_ref import COMPUTE_C, MEM_BIAS, MEM_SCALE, mxu_weight
from ..core.kernel_spec import COMPUTE_TILE, MXU_DIM, KernelSpec

# seeds the kernel state with ``start + acc * FOLD_BLOCK``: rounds to
# exactly ``start`` in float32 (acc < 2^20 keeps the increment below half
# an ulp of every start value used) but blocks XLA constant folding, so
# the kernel loop always executes at run time (see backends.body)
FOLD_BLOCK = 2.0**-46


def compute_step(a):
    """One paper compute-kernel iteration: A = A*A - C (one FMA/element)."""
    return a * a - COMPUTE_C


def memory_step(a):
    """One paper memory-kernel window update: read-scale-write."""
    return a * MEM_SCALE + MEM_BIAS


def mxu_step(b, w):
    """One MXU-kernel iteration: batched matmul, scaled back into orbit."""
    inv = jnp.float32(1.0 / MXU_DIM)
    return jnp.einsum("wij,jk->wik", b, w) * inv + b * jnp.float32(0.5)


def masked_loop(step_fn: Callable, state, iters, max_iters: int,
                dynamic: bool = False):
    """Run the kernel loop with per-column iteration counts.

    Static mode: ``max_iters`` steps with a per-column keep-old mask —
    what vectorized runtimes must do, and why they cannot exploit load
    imbalance (paper §V-G).  Dynamic mode: traced trip count
    (``while``-loop lowering) — per-task systems genuinely run fewer
    iterations for short tasks.  Values are bitwise identical.

    ``iters`` may be ``(W,)`` or ``(W, 1)``; ``state`` has leading W.
    """
    if dynamic:
        trip = jnp.max(iters)
        return jax.lax.fori_loop(0, trip, lambda k, st: step_fn(k, st), state)
    keep_shape = (state.shape[0],) + (1,) * (state.ndim - 1)

    def body(k, st):
        new = step_fn(k, st)
        keep = (k < iters).reshape(keep_shape)
        return jnp.where(keep, new, st)

    return jax.lax.fori_loop(0, max_iters, body, state)


def memory_geometry(kernel: KernelSpec) -> Tuple[int, int, int]:
    """(span, size, nwin) in f32 elements for the memory kernel's window
    walk — the single definition shared with ``core.kernel_ref``'s math."""
    span = max(1, kernel.span_bytes // 4)
    size = max(span, kernel.scratch_bytes // 4)
    size -= size % span  # whole number of windows
    return span, size, size // span


def memory_scratch_shape(kernel: KernelSpec, width: int) -> Tuple[int, int, int]:
    """The ``(nwin, W, span)`` VMEM scratch a Pallas caller gives the
    memory kernel (``mem_ref`` of ``run_kernel_columns``)."""
    span, _, nwin = memory_geometry(kernel)
    return nwin, width, span


def _memory_columns_ref(iters_col, seed_col, max_iters: int, mem_ref):
    """The memory kernel on a ``(nwin, W, span)`` ref: iteration ``k``
    rewrites window ``k % nwin`` — the value path's window walk, with the
    window picked by a leading-axis ref index instead of a dynamic slice."""
    nwin = mem_ref.shape[0]
    mem_ref[...] = jnp.broadcast_to((jnp.float32(1.0) + seed_col)[None],
                                    mem_ref.shape)

    def body(k, carry):
        j = k % nwin
        window = mem_ref[j]
        mem_ref[j] = jnp.where(k < iters_col, memory_step(window), window)
        return carry

    jax.lax.fori_loop(0, max_iters, body, 0)
    return mem_ref[0][:, 0:1]


def run_kernel_columns(kernel: KernelSpec, iters_col, seed_col,
                       max_iters: int, dynamic: bool = False,
                       mxu_w: Optional[jax.Array] = None, mem_ref=None):
    """The shared task-kernel body in column-vector form.

    ``iters_col``/``seed_col`` are ``(W, 1)``; returns ``(W, 1)`` f32
    results.  ``mxu_w`` lets Pallas callers pass the MXU weight as a ref
    value (kernels must not capture array constants); jitted callers leave
    it None and get the host-side constant.  ``mem_ref`` is the memory
    kernel's scratch for Pallas callers (``memory_scratch_shape``);
    jitted callers leave it None and walk a value.
    """
    width = seed_col.shape[0]

    if kernel.kind == "empty":
        # No work; preserve the data dependency so scheduling is honest.
        return seed_col * jnp.float32(0.0)

    if kernel.kind == "compute":
        tile = jnp.float32(0.5) + seed_col[:, :, None]
        tile = jnp.broadcast_to(tile, (width,) + COMPUTE_TILE)
        out = masked_loop(lambda k, a: compute_step(a), tile, iters_col,
                          max_iters, dynamic)
        return out[:, 0, :][:, 0:1]

    if kernel.kind == "compute_mxu":
        b = jnp.float32(0.25) + seed_col[:, :, None]
        b = jnp.broadcast_to(b, (width, MXU_DIM, MXU_DIM))
        w = jnp.asarray(mxu_weight()) if mxu_w is None else mxu_w
        out = masked_loop(lambda k, bb: mxu_step(bb, w), b, iters_col,
                          max_iters, dynamic)
        return out[:, 0, :][:, 0:1]

    if kernel.kind == "memory" and mem_ref is not None:
        return _memory_columns_ref(iters_col, seed_col, max_iters, mem_ref)

    if kernel.kind == "memory":
        span, size, nwin = memory_geometry(kernel)
        x = jnp.float32(1.0) + seed_col
        x = jnp.broadcast_to(x, (width, size))

        def step(k, st):
            wstart = (k % nwin) * span
            window = jax.lax.dynamic_slice(st, (0, wstart), (width, span))
            return jax.lax.dynamic_update_slice(st, memory_step(window),
                                                (0, wstart))

        out = masked_loop(step, x, iters_col, max_iters, dynamic)
        return out[:, 0:1]

    raise ValueError(kernel.kind)
