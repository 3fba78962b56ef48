"""Compile the chip path's kernels for a described TPU v5e, without a chip.

The TPU compiler is installed, so Mosaic and XLA:TPU can compile for a
``v5e:2x2`` topology that is described, not attached.  That refuses what
interpret mode accepts: unaligned blocks, rank-1 vector shape casts,
primitives with no Mosaic lowering, more VMEM or semaphore memory than a
core has.  Nothing runs, so these say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles.
"""
import functools
import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from repro.backends import get_backend  # noqa: E402
from repro.core import make_graph  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile_fused(graphs, sharding):
    be = get_backend("pallas-fused", interpret=False)
    built = (be._program_stacked(graphs, False) if len(graphs) > 1
             else be._program(graphs, False))
    fn, *args = built
    return fn.lower(*_shapes(args, sharding)).compile()


@pytest.mark.parametrize("pattern,kernel,width,ngraphs", [
    ("stencil", "compute", 16, 1),
    ("nearest", "compute", 32, 1),
    ("spread", "compute_mxu", 56, 1),  # the widest chip_smoke runs
    ("stencil", "memory", 16, 1),
    ("stencil", "compute", 8, 4),       # four stacked graphs, one launch
])
def test_pallas_fused_compiles(one_chip, pattern, kernel, width, ngraphs):
    kw = {"radix": 5} if pattern in ("nearest", "spread") else {}
    g = make_graph(width=width, height=1000, pattern=pattern, kernel=kernel,
                   **kw)
    text = _compile_fused([g] * ngraphs, one_chip).as_text()
    assert text.count("tpu_custom_call") >= 1
    # the kernel's name names its op in a device trace
    assert "%taskbench_megakernel" in text


def test_xla_scan_ops_carry_the_task_step_scopes(one_chip):
    """At the benchmark cell's size (W=128, H=1000) the compiled program's
    ops name the task step's parts, so a device trace can split them."""
    g = make_graph(width=128, height=1000, pattern="stencil", kernel="compute",
                   iterations=1)
    fn, *args = get_backend("xla-scan")._build([g])
    text = fn.lower(*_shapes(args, one_chip)).compile().as_text()
    ops = dict(re.findall(r'%([^\s=]+) = [^\n]*?op_name="([^"]*)"', text))
    assert any("fusion" in n and "/combine/" in o
               for n, o in ops.items())
    for scope in ("combine", "checksum", "kernel", "payload"):
        assert any(f"/{scope}/" in o for o in ops.values()), scope


def test_csp_exchange_ops_carry_the_exchange_scope(topo):
    """At the four-chip cell's size (W=512 over 4 ranks, H=1000, radix 5)
    the compiled rank program's collective ops, as the benchmark's trace
    reduction finds them, name the exchange: a device trace can tell it
    from the task step.  Its timestep loop runs 125 trips of 8 steps."""
    from chipbench.trace import COLLECTIVE

    mesh = Mesh(np.array(topo.devices[:4]), ("cols",))
    g = make_graph(width=512, height=1000, pattern="nearest", kernel="compute",
                   iterations=1, radix=5)
    be = get_backend("shardmap-csp", mesh=mesh)
    fn, (plan,) = be._program([g])
    assert plan.mode == "halo"
    on = lambda a, spec: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, spec))
    tables = jax.tree.map(on, be._tables(plan), be._table_specs())
    text = fn.lower((tables,)).compile().as_text()
    ops = re.findall(r'%([^\s=]+) = [^\n]*?op_name="([^"]*)"', text)
    collectives = [(n, o) for n, o in ops if COLLECTIVE.match(n)]
    assert {n.split(".")[0] for n, _ in collectives} == {
        "collective-permute-start", "collective-permute-done"}
    for n, o in collectives:
        assert "/exchange/" in o, (n, o)
    # the TPU compiler states no known trip count: read the bound the
    # outer loop's condition compares its counter with
    outer, = [line for line in text.splitlines()
              if " while(" in line and "/kernel/" not in line]
    cond = re.search(r"condition=(%[\w.-]+)", outer)[1]
    cond = text[text.index(f"\n{cond} ("):]
    assert re.findall(r"constant\((\d+)\)", cond[:cond.index("\n}")]) == [
        "125"]


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 16, 512, 64), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(flash_attention).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_compiles(one_chip):
    from repro.kernels.ssd import ssd_chunked

    # mamba2-2.7b widths: 80 heads of 64, state 128, one group
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(ssd_chunked).lower(
        S((1, 512, 80, 64), jnp.bfloat16), S((1, 512, 80), jnp.float32),
        S((80,), jnp.float32), S((1, 512, 1, 128), jnp.bfloat16),
        S((1, 512, 1, 128), jnp.bfloat16)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen_prefill_compiles_at_published_widths(one_chip):
    """The serving engine's prefill of qwen1.5-0.5b at full width fits one
    chip (weights, a 1024-row cache, activations)."""
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve.engine import _prefill_one

    cfg = get_config("qwen1.5-0.5b")
    params, _ = M.model_spec(cfg)
    fn = jax.jit(functools.partial(_prefill_one, cfg=cfg, max_len=1024))
    compiled = fn.lower(
        _shapes(params, one_chip),
        jax.ShapeDtypeStruct((1, 480), jnp.int32, sharding=one_chip)
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("pattern", ["stencil", "nearest"])
def test_pallas_fused_onesided_compiles_on_four_chips(topo, pattern):
    """One communicating kernel per rank, remote DMA puts, no XLA
    collective, at the size chip_smoke --chips 4 runs."""
    mesh = Mesh(np.array(topo.devices[:4]), ("cols",))
    kw = {"radix": 5} if pattern == "nearest" else {}
    g = make_graph(width=4 * 56, height=100, pattern=pattern, **kw)
    be = get_backend("pallas-fused[comm=onesided]", interpret=False,
                     mesh=mesh)
    fn, args = be._program_onesided([g], False)
    ranks, *tabs = args[0]
    on = lambda a, spec: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, spec))
    shapes = ((on(ranks, P("cols", None)),)
              + tuple(on(a, P("cols")) for a in tabs[:5])
              + tuple(on(a, P(None, None)) for a in tabs[5:]))
    traced = fn.trace([shapes])
    # the one-hot matmul that stages the rows to put must be exact: at the
    # MXU's default precision the 20-bit checksums came out wrong on chips
    assert "Precision.HIGHEST" in str(traced.jaxpr)
    text = traced.lower().compile().as_text()
    assert "tpu_custom_call" in text
    assert "%taskbench_megakernel_onesided" in text
    for op in ("all-gather", "all-to-all", "collective-permute"):
        assert op not in text, op
