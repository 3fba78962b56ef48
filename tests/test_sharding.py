"""Divisibility-fallback sharding rules (duck-typed mesh, no devices)."""
from types import SimpleNamespace

import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import ShardingRules, make_rules


def fake_mesh(**shape):
    return SimpleNamespace(shape=shape)


def rules_for(**shape):
    return make_rules(fake_mesh(**shape))


def test_basic_tp_fsdp():
    r = rules_for(data=16, model=16)
    assert r.spec_for(("embed", "heads", "head_dim"), (4096, 32, 128)) == \
        P("data", "model", None)
    assert r.spec_for(("embed", "ffn"), (4096, 14336)) == P("data", "model")


def test_heads_fallback_when_indivisible():
    """Arctic: 56 heads % 16 != 0 -> heads replicate (context-parallel)."""
    r = rules_for(data=16, model=16)
    assert r.spec_for(("embed", "heads", "head_dim"), (7168, 56, 128)) == \
        P("data", None, None)


def test_vocab_fallback_mamba():
    """Mamba-2 vocab 50280 % 16 != 0 -> embed dim picks up model axis."""
    r = rules_for(data=16, model=16)
    # vocab rule tries model and fails; embed falls back through data->...
    spec = r.spec_for(("vocab", "embed"), (50280, 2560))
    assert spec == P(None, "data")


def test_axis_used_once_per_tensor():
    r = rules_for(data=16, model=16)
    # both dims want 'model' (vocab + ffn-ish) - second one must skip it
    rules = dict(r.rules)
    rules["x1"] = ["model"]
    rules["x2"] = ["model", "data"]
    rr = ShardingRules(mesh=fake_mesh(data=16, model=16), rules=rules)
    assert rr.spec_for(("x1", "x2"), (32, 32)) == P("model", "data")


def test_kv_heads_replicate_when_small():
    r = rules_for(data=16, model=16)
    assert r.spec_for(("embed", "kv_heads", "head_dim"), (4096, 8, 128)) == \
        P("data", None, None)
    # 16 kv heads do shard
    assert r.spec_for(("embed", "kv_heads", "head_dim"), (1024, 16, 64)) == \
        P("data", "model", None)


def test_multipod_batch_axes():
    r = rules_for(pod=2, data=16, model=16)
    assert r.spec_for(("batch", "seq"), (256, 4096)) == \
        P(("pod", "data"), "model")
    # fsdp prefers the widest pod x data product when divisible
    assert r.spec_for(("embed", "ffn"), (8192, 29568)) == \
        P(("pod", "data"), "model")


def test_batch_one_replicates():
    r = rules_for(pod=2, data=16, model=16)
    assert r.spec_for(("batch", None), (1, 1)) == P(None, None)


def test_expert_sharding():
    r = rules_for(data=16, model=16)
    assert r.spec_for(("expert", "expert_embed", "expert_ffn"),
                      (128, 7168, 304)) == P("data", None, "model")
    # Mixtral virtualized to 16 sub-experts
    assert r.spec_for(("expert", "expert_embed", "expert_ffn"),
                      (16, 4096, 7168)) == P("data", None, "model")


def test_dp_only_strategy():
    r = make_rules(fake_mesh(data=16, model=16), strategy="dp_only")
    assert r.spec_for(("embed", "ffn"), (4096, 14336)) == P(None, None)
    assert r.spec_for(("batch", "seq"), (256, 4096)) == P("data", None)


def test_make_mesh_axes_accept_sharding_constraints():
    """Every mesh comes from launch.mesh.make_mesh with Auto axes, which
    ``constrain`` (with_sharding_constraint) accepts; jax.make_mesh's
    default Explicit axes are refused."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType

    from repro.dist.sharding import constrain, use_rules
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    with mesh, use_rules(make_rules(mesh)):
        y = jax.jit(lambda x: constrain(x, "batch", "embed") * 2)(
            jnp.ones((4, 8)))
    assert float(y.sum()) == 64.0
