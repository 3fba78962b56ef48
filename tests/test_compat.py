"""The shard_map/pcast shim, plus a guard against bypassing it."""
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import pcast, shard_map

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_shard_map_runs_on_one_device():
    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    f = shard_map(lambda v: jax.lax.psum(v, "d"),
                  mesh=mesh, in_specs=P("d"), out_specs=P("d"))
    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))


def test_shard_map_accepts_check_vma_kwarg():
    """check_vma passes through to jax.shard_map."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    f = shard_map(lambda v: v * 2, mesh=mesh, in_specs=P("d"),
                  out_specs=P("d"), check_vma=False)
    x = jnp.ones((4,), jnp.float32)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)), 2 * np.ones(4))


def test_pcast_is_usable_outside_shard_map_semantics():
    """pcast accepts a value that is already varying (a sharded input)
    and casts one that is not (a replicated input); values round-trip."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))

    def body(v, r):
        v = pcast(v, ("d",), to="varying")
        r = pcast(r, ("d",), to="varying")
        assert jax.typeof(r).vma == {"d"}
        return v + r

    f = shard_map(body, mesh=mesh, in_specs=(P("d"), P()), out_specs=P("d"))
    x = jnp.zeros((4,), jnp.float32)
    np.testing.assert_array_equal(np.asarray(f(x, jnp.ones((4,)))),
                                  np.ones(4))


def test_no_direct_jax_shard_map_references_in_src():
    """Everything under src/ must go through repro.compat."""
    import re

    banned = re.compile(
        r"jax\.shard_map"                       # attribute access
        r"|jax\.lax\.pcast|lax\.pcast"          # pcast in any spelling
        r"|jax\.experimental\.shard_map"        # legacy module, any form
        r"|from\s+jax\s+import\s+.*\bshard_map\b"
        r"|from\s+jax\.lax\s+import\s+.*\bpcast\b")
    offenders = []
    for root, _, files in os.walk(SRC):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            if os.path.basename(path) == "compat.py":
                continue
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    if banned.search(line.split("#", 1)[0]):
                        offenders.append(f"{path}:{lineno}")
    assert not offenders, (
        "direct jax shard_map/pcast use (import repro.compat "
        f"instead): {offenders}")
