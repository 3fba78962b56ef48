"""FlashAttention-2-style fused attention, Pallas TPU.

Forward-only fused attention with online softmax: grid is
``(batch, q_heads, q_blocks, k_blocks)`` with the k dimension minor-most
(sequential on TPU), accumulating into VMEM scratch.  Supports causal
masking, sliding windows (Mistral SWA), GQA head grouping, and a query
offset for chunked prefill against an existing KV cache.

Tiling: (block_q x d) query tiles and (block_k x d) key/value tiles staged
in VMEM; both default to 128 to keep the MXU contraction dims
hardware-aligned.  Out-of-window key blocks are masked rather than skipped
(a production TPU kernel would remap the k-grid; the skip is a pure
scheduling optimization with no semantic effect, see DESIGN.md).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # (1, 1, bq, d), (1, 1, bk, d), (1, 1, bk, d)
    o_ref,                # (1, 1, bq, d)
    acc_ref, m_ref, l_ref,  # VMEM scratch: (bq, d), (bq, 1), (bq, 1) f32
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    q_offset: int,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    kv_len: Optional[int],
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bq, bk)

    qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if kv_len is not None:  # zero-padded keys past the real length
        mask &= kpos < kv_len
    s = jnp.where(mask, s, NEG_INF)

    # row statistics stay (bq, 1): a rank-1 (bq,) value needs a shape cast
    # to broadcast against (bq, bk), which Mosaic cannot lower
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    # fully-masked rows: m_new stays NEG_INF; exp(NEG_INF - NEG_INF)=1 would
    # pollute l, so zero those rows explicitly.
    row_alive = m_new > NEG_INF / 2
    p = jnp.where(row_alive, p, 0.0)
    alpha = jnp.where(row_alive, alpha, 0.0)

    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Skv, D)
    v: jax.Array,  # (B, Hkv, Skv, D)
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    # lengths longer than a block and not a multiple of it are zero-padded
    # up to one; padded keys are masked, padded query rows sliced away
    pad_q, pad_k = (-Sq) % block_q, (-Skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq, nk = (Sq + pad_q) // block_q, (Skv + pad_k) // block_k
    scale = scale if scale is not None else float(1.0 / np.sqrt(D))

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
        kv_len=Skv if pad_k else None,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq] if pad_q else out
