"""The one place ``src/`` reaches ``jax.shard_map`` and ``jax.lax.pcast``.

All code under ``src/`` imports these names from here instead of
touching ``jax.shard_map`` / ``jax.lax.pcast`` directly (enforced by
``tests/test_compat.py``), so a change of either API is one edit.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map


def pcast(x, axis_names, to="varying"):
    """``jax.lax.pcast`` that accepts values already varying over the axes.

    ``jax.lax.pcast`` raises on a varying -> varying cast; inside a
    ``shard_map`` an input sharded over ``axis_names`` already is, and
    then there is nothing to cast.
    """
    if to == "varying" and set(axis_names) <= jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, axis_names, to=to)
