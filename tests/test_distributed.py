"""Multi-device semantics, each case in a subprocess with 8 host devices.

(The main pytest process must keep the default 1-device CPU runtime, so
anything needing a mesh larger than 1 runs via a child interpreter.)
"""
import os
import subprocess
import sys

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout


def test_csp_backend_multidevice():
    out = run_sub("""
import numpy as np
from repro.core import make_graph, check_outputs
from repro.backends import get_backend
for pat, kw in [("stencil", {}), ("spread", {"radix": 5}), ("fft", {})]:
    g = make_graph(width=16, height=8, pattern=pat, iterations=4,
                   output_bytes=64, **kw)
    be = get_backend("shardmap-csp")
    assert be.ndev == 8
    check_outputs(g, be.run([g])[0])
print("CSP8OK")
""")
    assert "CSP8OK" in out


@pytest.mark.parametrize("spec,many", [
    ("shardmap-csp", False),
    ("shardmap-csp[comm=onesided]", False),
    ("shardmap-csp[comm_overlap=True]", False),
    ("shardmap-csp", True),           # the combined prepare_many program
    ("shardmap-pipeline", False),
])
def test_rank_runner_moves_no_table_between_devices(spec, many):
    """The dependency tables are placed on the mesh when the runner is
    prepared: a run under a disallowing transfer guard copies none of
    them, and its outputs are the oracle's."""
    out = run_sub(f"""
import jax
from repro.core import make_graph, check_outputs
from repro.backends import get_backend
g = make_graph(width=16, height=8, pattern="nearest", iterations=4, radix=5)
be = get_backend({spec!r}, devices=jax.devices()[:4])
runner = be.prepare_many([g, g]) if {many!r} else be.prepare([g])
with jax.transfer_guard("disallow"):
    outs = runner()
    outs = runner()
assert len(outs) == (2 if {many!r} else 1)
for o in outs:
    check_outputs(g, o)
print("NOTRANSFEROK")
""", devices=4)
    assert "NOTRANSFEROK" in out


def test_backend_conformance_8dev():
    """The conformance matrix (every backend x every pattern) on 8 ranks."""
    out = run_sub("""
from repro.core import make_graph, check_outputs, execute_reference, pattern_names
from repro.backends import backend_names, get_backend
assert "shardmap-pipeline" in backend_names()
for pattern in pattern_names():
    kw = {"radix": 3} if pattern in ("nearest", "spread") else {}
    g = make_graph(width=8, height=6, pattern=pattern, iterations=3, **kw)
    expected = execute_reference(g)
    for be in backend_names():
        check_outputs(g, get_backend(be).run([g])[0], expected=expected)
print("CONFORM8OK")
""")
    assert "CONFORM8OK" in out


def test_ragged_width_multidevice():
    """Paper's MPI handles ragged columns: width 10 on 4 ranks, and a
    width smaller than the rank count (dead ranks)."""
    out = run_sub("""
import numpy as np, jax
from jax.sharding import Mesh
from repro.core import make_graph, check_outputs
from repro.backends import get_backend

mesh4 = Mesh(np.array(jax.devices()[:4]), ("cols",))
for pat, kw in [("stencil", {}), ("spread", {"radix": 3})]:
    g = make_graph(width=10, height=8, pattern=pat, iterations=4, **kw)
    be = get_backend("shardmap-csp", mesh=mesh4)
    plan = be.plan(g)
    assert plan.ragged and plan.padded_width == 12, plan
    check_outputs(g, be.run([g])[0])

# width 4 over 8 ranks: half the ranks hold only dead columns
g = make_graph(width=4, height=6, pattern="random", iterations=3)
check_outputs(g, get_backend("shardmap-csp").run([g])[0])
check_outputs(g, get_backend("shardmap-pipeline").run([g])[0])
print("RAGGEDOK")
""")
    assert "RAGGEDOK" in out


def test_run_many_combined_program_8dev():
    """The combined multi-graph shard_map program (one scan interleaving
    every graph's exchange+timestep) on 8 real ranks, ragged width, all
    three comm modes — bit-exact against single-graph runs."""
    out = run_sub("""
import numpy as np
from repro.core import make_graph, replicate, check_outputs, execute_reference
from repro.backends import get_backend
for bn in ("shardmap-csp", "shardmap-pipeline"):
    be = get_backend(bn)
    assert be.ndev == 8
    for pattern, kw in (("stencil", {}), ("sweep", {}),
                        ("spread", {"radix": 3})):
        g = make_graph(width=10, height=8, pattern=pattern, iterations=4, **kw)
        expected = execute_reference(g)
        alone = np.asarray(be.run([g])[0])
        outs = be.run_many(replicate(g, 3))
        assert len(outs) == 3
        for o in outs:
            check_outputs(g, o, expected=expected)
            assert (np.asarray(o)[:, :4] == alone[:, :4]).all()
print("RUNMANY8OK")
""")
    assert "RUNMANY8OK" in out


def test_pipeline_backend_ring_8dev():
    """Sweep-class graphs ride the one-directional ppermute ring."""
    out = run_sub("""
from repro.core import make_graph, check_outputs
from repro.backends import get_backend
be = get_backend("shardmap-pipeline")
assert be.ndev == 8
for width in (8, 16):
    g = make_graph(width=width, height=10, pattern="sweep", iterations=4,
                   output_bytes=64)
    plan = be.plan(g)
    assert plan.mode == "ring", plan.mode
    check_outputs(g, be.run([g])[0])
print("RING8OK")
""")
    assert "RING8OK" in out


def test_pp_forward_4d_mesh():
    """pp_forward through a (pod, data, model, stage) mesh == reference."""
    out = run_sub("""
import dataclasses, jax, numpy as np
from repro.configs import get_config, reduced
from repro.dist import pipeline as PP
from repro.dist.sharding import make_rules, use_rules
from repro.models import model as M
from repro.models.layers import split_leaves

cfg = dataclasses.replace(reduced(get_config("yi-6b")), num_layers=4)
params, _ = split_leaves(M.init_model(jax.random.PRNGKey(0), cfg))
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
ref_logits, _, _ = M.forward(params, cfg, tokens=toks)

from repro.launch.mesh import make_mesh
mesh = make_mesh((1, 2, 2, 2), ("pod", "data", "model", "stage"))
rules = make_rules(mesh)
pp_params = PP.stack_params_by_stage(params, num_stages=2)
with mesh, use_rules(rules):
    pp_logits = jax.jit(
        lambda p, t: PP.pp_forward(p, cfg, t, 2, 4))(pp_params, toks)
np.testing.assert_allclose(np.asarray(pp_logits, np.float32),
                           np.asarray(ref_logits, np.float32),
                           rtol=2e-3, atol=2e-3)
print("PP4DOK")
""")
    assert "PP4DOK" in out


def test_dp_train_step_8dev():
    """shard_map'd DP step == reference step; compressed within tolerance."""
    out = run_sub("""
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.data.pipeline import DataConfig, make_batch
from repro.train import train_step as TS, dist_step as DS

cfg = reduced(get_config("qwen1.5-0.5b"))
tcfg = TS.TrainConfig(base_lr=1e-3, warmup_steps=2, total_steps=40)
dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=16)
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))

def run(fn, steps=3):
    state, _ = TS.init_state(jax.random.PRNGKey(0), cfg, tcfg)
    losses = []
    for s in range(steps):
        state, m = fn(state, make_batch(dcfg, s))
        losses.append(float(m["loss"]))
    return state, losses

s_ref, l_ref = run(TS.jit_train_step(cfg, tcfg))
s_ex, l_ex = run(DS.jit_dp_train_step(cfg, tcfg, mesh, compress=False))
s_c, l_c = run(DS.jit_dp_train_step(cfg, tcfg, mesh, compress=True))
np.testing.assert_allclose(l_ex, l_ref, atol=1e-4)
np.testing.assert_allclose(l_c, l_ref, atol=2e-2)
for a, b in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_ex.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
for a, b in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_c.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-2)
print("DPSTEP8OK")
""")
    assert "DPSTEP8OK" in out


def test_csp_forced_a2a_8dev():
    """The per-pair a2a exchange (CommPlan mode "a2a") on 8 real ranks,
    including ragged width and local>1 blocks."""
    out = run_sub("""
from repro.core import make_graph, check_outputs
from repro.backends import get_backend
be = get_backend("shardmap-csp", comm="a2a")
assert be.ndev == 8
for pat, kw, width in [("stencil", {}, 16), ("spread", {"radix": 3}, 10),
                       ("fft", {}, 16), ("sweep", {}, 4)]:
    g = make_graph(width=width, height=8, pattern=pat, iterations=4, **kw)
    plan = be.plan(g)
    assert plan.mode == "a2a"
    assert (plan.recv_counts == plan.send_counts.T).all()
    check_outputs(g, be.run([g])[0])
print("A2A8OK")
""")
    assert "A2A8OK" in out


def test_moe_sp_matches_replicated_8rank():
    """SP-aware EP == token replication == dense on an 8-rank (data x
    model) mesh — forward and parameter gradients — and the explicit
    ep_mode plumbing through apply_moe/cfg agrees with the config
    default (mixtral ships ep_mode="sp")."""
    out = run_sub("""
import dataclasses, jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.dist.sharding import make_rules, use_rules
from repro.models import moe as MO
from repro.models.layers import split_leaves

cfg = dataclasses.replace(reduced(get_config("mixtral-8x7b")),
                          moe_capacity_factor=8.0)
assert cfg.ep_mode == "sp"
for shape in ((4, 2), (2, 4)):
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(shape, ("data", "model"))
    rules = make_rules(mesh)
    params, _ = split_leaves(MO.init_moe(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model),
                          jnp.float32)
    y_dense, m_d = MO.apply_moe(params, x, cfg, impl="dense")
    with mesh, use_rules(rules):
        run = lambda mode: jax.jit(lambda p, xx: MO.apply_moe(
            p, xx, cfg, impl="a2a", ep_mode=mode))(params, x)
        y_sp, m_sp = run("sp")
        y_rep, m_rep = run("replicated")
        y_cfg, _ = jax.jit(lambda p, xx: MO.apply_moe(
            p, xx, cfg, impl="a2a"))(params, x)  # cfg default -> sp
    scale = np.abs(np.asarray(y_dense)).max()
    tol = 5e-4 * max(scale, 1)  # same tolerance as test_moe_a2a_matches_dense
    assert np.abs(np.asarray(y_sp) - np.asarray(y_rep)).max() < tol, shape
    assert np.abs(np.asarray(y_sp) - np.asarray(y_dense)).max() < tol, shape
    assert np.abs(np.asarray(y_cfg) - np.asarray(y_sp)).max() == 0.0, shape
    assert abs(float(m_sp["moe_lb_loss"]) - float(m_rep["moe_lb_loss"])) < 1e-3

    def loss(impl, mode=None):
        def f(p):
            y, _ = MO.apply_moe(p, x, cfg, impl=impl, ep_mode=mode)
            return (y.astype(jnp.float32) ** 2).mean()
        return f
    g_dense = jax.grad(loss("dense"))(params)
    with mesh, use_rules(rules):
        g_sp = jax.jit(jax.grad(loss("a2a", "sp")))(params)
    for k in ("w_gate", "w_up", "w_down"):
        np.testing.assert_allclose(np.asarray(g_sp[k], np.float32),
                                   np.asarray(g_dense[k], np.float32),
                                   rtol=5e-3, atol=5e-5)
print("MOESPOK")
""")
    assert "MOESPOK" in out


def test_moe_dispatch_roofline_8dev():
    """Acceptance gate: the compiled MoE program's per-plane all-to-all
    bytes (dry-run roofline over optimized HLO) drop by exactly |model|
    under SP-aware EP, and match the analytic capacity model."""
    out = run_sub("""
from repro.bench import MoEDispatchSpec, moe_dispatch_report
for data, model in ((4, 2), (2, 4)):
    reps = {}
    for ep_mode in ("replicated", "sp"):
        spec = MoEDispatchSpec(data=data, model=model, ep_mode=ep_mode)
        rep = moe_dispatch_report(spec, compiled=True)
        # the compiled program moves exactly the planned bytes
        assert rep["hlo_a2a_bytes"] == rep["a2a_bytes"], (ep_mode, rep)
        reps[ep_mode] = rep
    # per-plane a2a volume reduced by the model axis size
    assert reps["replicated"]["hlo_a2a_bytes"] == \\
        reps["sp"]["hlo_a2a_bytes"] * model, (data, model)
    # sp trades the duplicated a2a for one over-model all-gather
    assert reps["sp"]["hlo_allgather_bytes"] > 0
    assert reps["replicated"]["hlo_allgather_bytes"] == 0
print("MOEDISPATCHOK")
""")
    assert "MOEDISPATCHOK" in out


def test_moe_a2a_matches_dense():
    out = run_sub("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduced
from repro.dist.sharding import make_rules, use_rules
from repro.models import moe as MO
from repro.models.layers import split_leaves
import dataclasses

cfg = reduced(get_config("mixtral-8x7b"))
cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)  # no drops
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
rules = make_rules(mesh)
p_leaf = MO.init_moe(jax.random.PRNGKey(0), cfg)
params, _ = split_leaves(p_leaf)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model), jnp.float32)

y_dense, m1 = MO.apply_moe(params, x, cfg, impl="dense")
with mesh, use_rules(rules):
    y_a2a, m2 = jax.jit(lambda p, xx: MO.apply_moe(p, xx, cfg, impl="a2a"))(params, x)
err = np.abs(np.asarray(y_a2a) - np.asarray(y_dense)).max()
scale = np.abs(np.asarray(y_dense)).max()
print("moe err", err, "scale", scale)
assert err < 5e-4 * max(scale, 1), err
assert abs(float(m1["moe_lb_loss"]) - float(m2["moe_lb_loss"])) < 1e-3
print("MOEOK")
""")
    assert "MOEOK" in out


def test_moe_a2a_grads_match_dense():
    out = run_sub("""
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.dist.sharding import make_rules, use_rules
from repro.models import moe as MO
from repro.models.layers import split_leaves
import dataclasses

cfg = dataclasses.replace(reduced(get_config("mixtral-8x7b")),
                          moe_capacity_factor=8.0)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
rules = make_rules(mesh)
params, _ = split_leaves(MO.init_moe(jax.random.PRNGKey(0), cfg))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model))

def loss(impl):
    def f(p):
        y, _ = MO.apply_moe(p, x, cfg, impl=impl)
        return (y.astype(jnp.float32) ** 2).mean()
    return f

g_dense = jax.grad(loss("dense"))(params)
with mesh, use_rules(rules):
    g_a2a = jax.jit(jax.grad(loss("a2a")))(params)
for k in ("w_gate", "w_up", "w_down"):
    a, b = np.asarray(g_a2a[k], np.float32), np.asarray(g_dense[k], np.float32)
    np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-5)
print("MOEGRADOK")
""")
    assert "MOEGRADOK" in out


def test_compressed_psum():
    out = run_sub("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map
from repro.dist.compression import compressed_psum
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("d",))
x = np.random.RandomState(0).randn(8, 64).astype(np.float32)
f = jax.jit(shard_map(lambda v: compressed_psum(v, "d"),
    mesh=mesh, in_specs=P("d"), out_specs=P("d")))
got = np.asarray(f(jnp.asarray(x)))
want = x.sum(0, keepdims=True)
scale = np.abs(x).max() / 127.0
assert np.abs(got - want).max() <= 8 * scale * 0.51 + 1e-6
print("PSUMOK")
""")
    assert "PSUMOK" in out


def test_dryrun_machinery_tiny_mesh():
    """The dry-run driver end-to-end on a (2,2,2) pod mesh, reduced arch."""
    out = run_sub("""
import jax, jax.numpy as jnp, functools
from repro.configs import get_config, reduced, SHAPES, InputShape
from repro.dist.sharding import make_rules, use_rules
from repro.launch import specs as SP
from repro.launch.roofline import analyze_hlo
from repro.optim import adamw
from repro.train import train_step as TS

cfg = reduced(get_config("yi-6b"))
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rules = make_rules(mesh)
shape = InputShape("tiny_train", 64, 8, "train")
with mesh, use_rules(rules):
    tcfg = TS.TrainConfig(grad_accum=2, adamw=adamw.AdamWConfig())
    state, axes = SP.state_struct(cfg, tcfg)
    st_sh = SP.shardings_from_axes(axes, state, rules)
    batch, baxes = SP.batch_struct(cfg, shape)
    b_sh = SP.shardings_from_axes(baxes, batch, rules)
    fn = functools.partial(TS.train_step, cfg=cfg, tcfg=tcfg)
    compiled = jax.jit(fn, donate_argnums=(0,), in_shardings=(st_sh, b_sh),
                       out_shardings=(st_sh, None)).lower(state, batch).compile()
mem = compiled.memory_analysis()
a = analyze_hlo(compiled.as_text())
assert a["flops"] > 0 and a["collectives"]["total"] > 0
print("DRYRUNOK", mem.temp_size_in_bytes, int(a["flops"]))
""", devices=8)
    assert "DRYRUNOK" in out
