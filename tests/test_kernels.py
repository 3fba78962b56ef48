"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernel_ref import run_kernel_ref
from repro.core.kernel_spec import KernelSpec
from repro.kernels import ops, ref


@pytest.mark.parametrize("width,max_iters", [(8, 12), (16, 40), (32, 7)])
def test_taskbench_compute_kernel(width, max_iters):
    tiles = jnp.full((width, 8, 128), 0.5, jnp.float32)
    iters = jnp.asarray(
        np.random.RandomState(width).randint(1, max_iters + 1, width),
        jnp.int32)
    out_k = ops.taskbench_compute(tiles, iters, max_iters, impl="interpret")
    out_r = ops.taskbench_compute(tiles, iters, max_iters, impl="ref")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6)
    exp = np.array([run_kernel_ref(KernelSpec(kind="compute"), int(i))
                    for i in iters], np.float32)
    np.testing.assert_allclose(np.asarray(out_k)[:, 0, 0], exp, rtol=1e-6)


@pytest.mark.parametrize("size,span,iters", [(1024, 128, 7), (2048, 256, 0),
                                             (512, 512, 9)])
def test_taskbench_memory_kernel(size, span, iters):
    x = jnp.arange(size, dtype=jnp.float32) / size
    a = ops.taskbench_memory(x, iters, span, impl="interpret")
    b = ops.taskbench_memory(x, iters, span, impl="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


ATTN_CASES = [
    # B, Sq, Skv, Hq, Hkv, D, causal, window, q_offset, dtype
    (2, 128, 128, 4, 2, 64, True, None, 0, jnp.float32),
    (1, 128, 256, 8, 8, 32, True, 64, 128, jnp.float32),
    (2, 64, 64, 4, 1, 64, False, None, 0, jnp.float32),
    (1, 256, 256, 2, 2, 128, True, 128, 0, jnp.bfloat16),
    (2, 128, 128, 6, 3, 64, True, None, 0, jnp.float32),
    # lengths that are not a multiple of the block: padded and masked
    (1, 200, 200, 4, 2, 64, True, None, 0, jnp.float32),
    (2, 100, 150, 4, 4, 32, False, None, 0, jnp.float32),
]


@pytest.mark.parametrize("case", range(len(ATTN_CASES)))
def test_flash_attention_kernel(case):
    B, Sq, Skv, Hq, Hkv, D, causal, win, qoff, dt = ATTN_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(case), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dt)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), dt)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), dt)
    o_k = ops.attention(q, k, v, causal=causal, window=win, q_offset=qoff,
                        impl="interpret", block_q=64, block_k=64)
    o_r = ops.attention(q, k, v, causal=causal, window=win, q_offset=qoff,
                        impl="ref")
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r, np.float32),
        rtol=tol, atol=tol)


def test_attention_chunked_matches_dense():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 2048, 4, 32))
    k = jax.random.normal(ks[1], (1, 2048, 2, 32))
    v = jax.random.normal(ks[2], (1, 2048, 2, 32))
    a = ref.attention_ref(q, k, v, causal=True, window=512)
    b = ref.attention_ref_chunked(q, k, v, causal=True, window=512,
                                  q_chunk=256)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


SSD_CASES = [
    # B, S, H, P, G, N, chunk
    (2, 128, 4, 16, 2, 8, 32),
    (1, 256, 8, 32, 1, 16, 64),
    (2, 64, 2, 64, 2, 32, 64),
]


@pytest.mark.parametrize("case", range(len(SSD_CASES)))
def test_ssd_kernel(case):
    B, S, H, P, G, N, chunk = SSD_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(case), 6)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    D = jax.random.normal(ks[5], (H,))
    y_k, h_k = ops.ssd(x, dt, A, Bm, Cm, D, chunk=chunk, impl="interpret")
    y_s, h_s = ref.ssd_ref(x, dt, A, Bm, Cm, D, return_state=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_s), rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_s), rtol=2e-3,
                               atol=2e-3)


def test_ssd_ragged_padding():
    """ops.ssd pads to chunk multiples without corrupting the final state."""
    B, S, H, P, G, N = 1, 100, 2, 8, 1, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    y_p, h_p = ops.ssd(x, dt, A, Bm, Cm, chunk=32, impl="ref")
    y_s, h_s = ref.ssd_ref(x, dt, A, Bm, Cm, return_state=True)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_s), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_s), rtol=2e-4,
                               atol=2e-4)


def test_ssd_decode_step_matches_scan():
    B, S, H, P, G, N = 2, 16, 2, 8, 1, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    y_full, h_full = ref.ssd_ref(x, dt, A, Bm, Cm, return_state=True)
    h = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        y_t, h = ops.ssd_decode_step(
            x[:, t:t+1], dt[:, t:t+1], A, Bm[:, t:t+1], Cm[:, t:t+1], h)
        ys.append(y_t)
    y_dec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_full),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full),
                               rtol=2e-4, atol=2e-4)
