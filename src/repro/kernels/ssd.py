"""Mamba-2 SSD (state-space duality) chunked kernel, Pallas TPU.

Implements the matmul-form SSD algorithm (Dao & Gu, arXiv:2405.21060) the
way a TPU wants it: the sequence is split into chunks of ``chunk``
positions; intra-chunk work is three MXU matmuls over (chunk x chunk) and
(chunk x state) tiles staged in VMEM, and the inter-chunk recurrence is a
scalar-decay update on a persistent (headdim x state) VMEM scratch carried
across the sequential chunk grid dimension.

Grid: (batch, heads, n_chunks) — chunks minor-most so the state scratch
walks the sequence in order.  The cumulative within-chunk log-decay is
computed with a lower-triangular matmul (MXU) rather than a serial cumsum.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,   # (1, 1, Q, P)
    dt_ref,  # (1, 1, Q, 1)
    a_ref,   # (H,) in SMEM
    b_ref,   # (1, 1, Q, N)
    c_ref,   # (1, 1, Q, N)
    y_ref,   # (1, 1, Q, P) out
    hT_ref,  # (1, 1, P, N) out (final state)
    h_ref,   # VMEM scratch (P, N) f32
    *,
    chunk: int,
    num_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)    # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (Q, 1)
    a = a_ref[pl.program_id(1)]            # scalar (SMEM)
    b = b_ref[0, 0].astype(jnp.float32)    # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)    # (Q, N)

    la = dt * a  # (Q, 1) negative log-decays
    # inclusive cumsum via lower-triangular matmul (MXU-friendly)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = rows >= cols
    cum = jax.lax.dot_general(
        tri.astype(jnp.float32), la, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q, 1)
    # the same values as a row: a diagonal select and a sublane sum, so no
    # (Q, 1) -> (1, Q) transpose is needed
    cum_row = jnp.where(rows == cols, cum, 0.0).sum(axis=0, keepdims=True)

    # intra-chunk: scores[i,j] = (c_i . b_j) * exp(cum_i - cum_j) for j <= i
    decay = jnp.where(tri, jnp.exp(cum - cum_row), 0.0)
    cb = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    xdt = x * dt
    y = jax.lax.dot_general(
        cb * decay, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q, P)

    # inter-chunk: y += (c * exp(cum)) @ h_prev^T
    h = h_ref[...]  # (P, N)
    c_in = c * jnp.exp(cum)
    y += jax.lax.dot_general(
        c_in, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    # state update: h = exp(cum[-1]) * h + x^T @ (b * (exp(cum[-1]-cum)*dt))
    last = jnp.sum(la)  # == cum[-1], as a scalar: a (1, 1) value cannot
    #                     broadcast over sublanes and lanes at once
    b_in = b * (jnp.exp(last - cum) * dt)
    h_new = jnp.exp(last) * h + jax.lax.dot_general(
        x, b_in, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)
    h_ref[...] = h_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == num_chunks - 1)
    def _final():
        hT_ref[0, 0] = h_new


def ssd_chunked(
    x: jax.Array,   # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    A: jax.Array,   # (H,)
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    D: Optional[jax.Array] = None,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final state (B,H,P,N)); zero initial state.

    The kernel runs head-major — (B, H, S, P), with dt as a (B, H, S, 1)
    column — so every block's last two dims are (chunk, features): the
    (8, 128) tiling Mosaic requires, which a (1, P) head slice is not.
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert H % G == 0
    rep = H // G
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    heads_major = lambda a: jnp.swapaxes(a, 1, 2)  # (B, S, X, .) -> (B, X, S, .)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)
    y, hT = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h // rep, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h // rep, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(heads_major(x), heads_major(dt[..., None]), A.astype(jnp.float32),
      heads_major(Bm), heads_major(Cm))
    y = heads_major(y)
    if D is not None:
        y = (y.astype(jnp.float32)
             + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
             ).astype(x.dtype)
    return y, hT
