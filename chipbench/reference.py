"""Plain reference of a Task Bench graph, and the comparison that decides
``correct``.

The reference follows the paper's task semantics (§II) and imports
nothing of the program.  Every task (t, i) emits a payload row

    [t, i, base(t, i), combined(t, i), result, result, ...]

with ``base`` a hash of the coordinates reduced mod 2^20, ``combined``
that hash plus the sum of its dependencies' combined values (mod 2^20),
and ``result`` the compute kernel run for ``iterations`` steps:
A <- A*A - 1 from A = 0.5, one multiply and one subtract per element of
an (8, 128) tile.  Every element of the tile takes the same path, so one
scalar stands for the tile.  Dependencies are fixed column offsets
(``dep_offsets`` of the configuration), clipped at the graph's edges.

``dtype`` is what payload values are stored in and the kernel computes
in: float32 is the configuration's precision; bfloat16 is the control.
"""
from __future__ import annotations

from typing import Dict, Sequence

import ml_dtypes
import numpy as np

MOD = 1 << 20
BFLOAT16 = np.dtype(ml_dtypes.bfloat16)


def _stored(values: np.ndarray, dtype: np.dtype) -> np.ndarray:
    """``values`` as ``dtype`` holds them, read back exactly."""
    return values.astype(dtype).astype(np.float64)


def kernel_result(iterations: int, dtype=np.float32) -> float:
    dt = np.dtype(dtype)
    a = np.asarray(0.5, dt)
    one = np.asarray(1.0, dt)
    for _ in range(iterations):
        a = (a * a).astype(dt)
        a = (a - one).astype(dt)
    return float(a)


def final_payload(width: int, height: int, offsets: Sequence[int],
                  iterations: int, payload_elems: int,
                  dtype=np.float32) -> np.ndarray:
    """The (width, payload_elems) payloads of the graph's last timestep,
    as float32."""
    dt = np.dtype(dtype)
    cols = np.arange(width, dtype=np.int64)
    prev = None
    for t in range(height):
        base = ((t * 2654435761 + cols * 40503) % (1 << 32)) % MOD
        acc = np.zeros(width, np.int64)
        if t > 0:
            for off in offsets:
                src = cols + off
                ok = (src >= 0) & (src < width)
                acc[ok] += prev[src[ok]]
            acc %= MOD
        combined = (base + acc) % MOD
        prev = _stored(combined, dt).astype(np.int64)
    out = np.empty((width, payload_elems), np.float64)
    out[:, 0] = height - 1
    out[:, 1] = cols
    out[:, 2] = base
    out[:, 3] = combined
    out[:, 4:] = kernel_result(iterations, dt)
    return _stored(out, dt).astype(np.float32)


def compare(outputs: Sequence[np.ndarray], expected: np.ndarray,
            kernel_limit: float) -> Dict[str, float]:
    """Compare every run's output with the reference.

    Slots 0-3 (coordinates and checksums) must be equal: each differing
    value counts in ``mismatches``.  Slots 4 and on (the kernel result)
    are held to ``kernel_limit`` by absolute difference.  A run whose
    output has the wrong shape counts every value as mismatched.
    Returns ``runs``, ``failed_runs``, ``mismatches`` and
    ``kernel_abs_err`` (the largest over all runs).
    """
    runs = failed = mismatches = 0
    worst = 0.0
    for got in outputs:
        got = np.asarray(got, np.float32)
        runs += 1
        if got.shape != expected.shape:
            failed += 1
            mismatches += expected.size
            continue
        bad = int((got[:, :4] != expected[:, :4]).sum())
        err = float(np.abs(got[:, 4:] - expected[:, 4:]).max(initial=0.0))
        if not np.isfinite(err):
            err = float("inf")
        mismatches += bad
        worst = max(worst, err)
        if bad or err > kernel_limit:
            failed += 1
    return {"runs": runs, "failed_runs": failed, "mismatches": mismatches,
            "kernel_abs_err": worst}
