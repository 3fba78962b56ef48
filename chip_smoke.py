#!/usr/bin/env python3
"""Bring-up run of the main paths on a TPU, every result checked.

    python chip_smoke.py            # one chip: Task Bench matrix + serving
    python chip_smoke.py --chips 4  # four chips: the multi-rank backends only

One chip:

* Task Bench: ``xla-scan``, ``xla-static``, ``host-dynamic`` and
  ``pallas-fused`` (compiled, never interpret mode) over stencil,
  nearest r5 and spread r5 with the compute, compute_mxu and memory
  kernels, plus four concurrent graphs on ``xla-scan`` and
  ``pallas-fused``.  Every output passes ``check_outputs`` against the
  numpy oracle.
* Serving: qwen1.5-0.5b at its published widths (random bf16 weights from
  a seed) through ``ServeEngine``.  Every generated token is checked
  against a float32 reference forward, teacher-forced on the engine's
  output.

Four chips: ``shardmap-csp`` (blocking and one-sided) and
``pallas-fused[comm=onesided]`` on a mesh of ``jax.devices()[:4]``, each
output checked against the oracle and against ``xla-scan`` on one chip,
and the weak-scaling sweep over sub-meshes of 1, 2 and 4 chips.

Everything runs in this one process: a chip belongs to one process at a
time.  Times printed on the way are bring-up observations, not metrics.
The last line of stdout is ``{"ok": true, "device": {...}}``; without a
TPU, or when any phase fails, the script prints no such line and exits
nonzero.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback
from typing import Callable, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.backends import get_backend  # noqa: E402
from repro.bench.scaling import ScalingSpec, run_scaling  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import (TaskGraph, check_outputs, execute_reference,  # noqa: E402
                        make_graph, replicate)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.layers import split_leaves  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402

PATTERNS = (("stencil", {}), ("nearest", {"radix": 5}),
            ("spread", {"radix": 5}))
KERNELS = ("compute", "compute_mxu", "memory")
# a served token's float32 reference logit may sit this far below the
# position's maximum (logit units; the reference logits' std is about 1).
# bf16 rounding moved it by at most 0.0084 on the same widths cut to 2 and
# 6 layers; a wrong token sits several units below.
LOGIT_TOL = 0.15

Log = Callable[[str], None]


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ Task Bench
@dataclasses.dataclass(frozen=True)
class TaskBenchSizes:
    width: int = 128          # xla-scan, xla-static
    height: int = 1000
    host_height: int = 100    # host-dynamic dispatches every task from the host
    # pallas-fused holds whole dependency tables in VMEM: the widest that
    # compile for v5e at H=1000 (W=64 is refused; the memory kernel also
    # holds a 1 MiB scratch per column; stacked graphs multiply the tables)
    fused_width: int = 56
    fused_memory_width: int = 16
    fused_concurrent_width: int = 8
    concurrent: int = 4


@dataclasses.dataclass
class Job:
    name: str
    backend: str
    graphs: List[TaskGraph]
    many: bool = False  # run_many: the graphs run concurrently


def taskbench_jobs(sizes: TaskBenchSizes,
                   fused: str = "pallas-fused") -> List[Job]:
    s = sizes
    jobs = []
    for pattern, kw in PATTERNS:
        for kernel in KERNELS:
            cell = f"{pattern}{kw.get('radix', '')}/{kernel}"
            g = make_graph(s.width, s.height, pattern, kernel, **kw)
            for be in ("xla-scan", "xla-static"):
                jobs.append(Job(f"{be} {cell} W={s.width} H={s.height}",
                                be, [g]))
            gh = make_graph(s.width, s.host_height, pattern, kernel, **kw)
            jobs.append(Job(f"host-dynamic {cell} W={s.width} "
                            f"H={s.host_height}", "host-dynamic", [gh]))
            fw = s.fused_memory_width if kernel == "memory" else s.fused_width
            gf = make_graph(fw, s.height, pattern, kernel, **kw)
            jobs.append(Job(f"{fused} {cell} W={fw} H={s.height}", fused,
                            [gf]))
    n = s.concurrent
    g = make_graph(s.width, s.height, "stencil", "compute")
    jobs.append(Job(f"xla-scan {n}x stencil/compute W={s.width} "
                    f"H={s.height}", "xla-scan", replicate(g, n), many=True))
    gf = make_graph(s.fused_concurrent_width, s.height, "stencil", "compute")
    jobs.append(Job(f"{fused} {n}x stencil/compute "
                    f"W={s.fused_concurrent_width} H={s.height}", fused,
                    replicate(gf, n), many=True))
    return jobs


def check_fused(backend, graphs: Sequence[TaskGraph],
                expect_interpret: bool) -> None:
    """A pallas-fused run is the Mosaic kernel, not a quiet fallback."""
    if backend.interpret is not expect_interpret:
        raise AssertionError(f"pallas-fused interpret={backend.interpret}, "
                             f"expected {expect_interpret}")
    if "tpu_custom_call" not in backend.lowered_stablehlo(graphs):
        raise AssertionError("pallas-fused lowering holds no Pallas kernel "
                             "(tpu_custom_call)")


def run_taskbench(sizes: TaskBenchSizes, fused: str = "pallas-fused",
                  expect_interpret: bool = False,
                  log: Log = say) -> List[str]:
    """Run every Task Bench job in turn and check it; returns the failed
    job names.

    The jobs go pattern by pattern.  ``xla-static``'s program depends on
    the kernel, not the pattern, so with the persistent compilation cache
    on, the later patterns load the first one's programs.
    """
    oracle = functools.lru_cache(maxsize=None)(execute_reference)
    failures = []
    for job in taskbench_jobs(sizes, fused):
        try:
            be = get_backend(job.backend)
            if job.backend.startswith("pallas-fused"):
                check_fused(be, job.graphs, expect_interpret)
            t0 = time.perf_counter()
            runner = (be.prepare_many if job.many else be.prepare)(job.graphs)
            prep_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            outs = runner()
            run_s = time.perf_counter() - t0
            if len(outs) != len(job.graphs):
                raise AssertionError(f"{len(outs)} outputs for "
                                     f"{len(job.graphs)} graphs")
            for g, out in zip(job.graphs, outs):
                check_outputs(g, out, expected=oracle(g))
            log(f"  ok    {job.name}: compile {prep_s:.1f} s, "
                f"run {run_s:.3f} s")
        except Exception:
            failures.append(job.name)
            log(f"  FAIL  {job.name}\n{traceback.format_exc()}")
    return failures


# --------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("cfg",))
def _reference_scores(params, tokens, generated, *, cfg):
    """For each generated token: its reference logit, the position's max
    logit and argmax.  ``tokens`` is prompt + generated[:-1]."""
    n = generated.shape[0]
    logits = M.forward(params, cfg, tokens=tokens)[0][0, -n:]
    logits = logits.astype(jnp.float32)
    chosen = jnp.take_along_axis(logits, generated[:, None], axis=1)[:, 0]
    return chosen, logits.max(axis=-1), logits.argmax(axis=-1)


def reference_check(cfg, params, prompt: np.ndarray, generated: Sequence[int],
                    tol: float) -> Tuple[float, float]:
    """Teacher-force the float32 reference on the engine's output.

    Returns (largest gap below the max logit, exact-argmax share); raises
    when a gap exceeds ``tol``.
    """
    cfg_ref = dataclasses.replace(cfg, kernel_impl="ref", dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    gen = np.asarray(generated, np.int32)
    tokens = np.concatenate([np.asarray(prompt, np.int32), gen[:-1]])[None]
    with jax.default_matmul_precision("highest"):
        chosen, top, arg = _reference_scores(
            p32, jnp.asarray(tokens), jnp.asarray(gen), cfg=cfg_ref)
    gaps = np.asarray(top) - np.asarray(chosen)
    worst = float(gaps.max())
    if worst > tol:
        k = int(gaps.argmax())
        raise AssertionError(
            f"generated token {k} ({gen[k]}) has reference logit "
            f"{float(np.asarray(chosen)[k]):.4f}, {worst:.4f} below the "
            f"max {float(np.asarray(top)[k]):.4f} (tolerance {tol})")
    return worst, float((np.asarray(arg) == gen).mean())


def attention_paths(engine: ServeEngine, prompt_len: int) -> Tuple[str, str]:
    """Which attention the engine's compiled prefill and decode run:
    "pallas" when the lowering holds a Pallas kernel, else "xla"."""
    def which(text):
        return "pallas" if "tpu_custom_call" in text else "xla"

    slots = engine.slots
    pf = engine._prefill1.lower(
        engine.params, jnp.zeros((1, prompt_len), jnp.int32)).as_text()
    dec = engine._chunk.lower(
        engine.params, engine.cur, engine.caches,
        jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.int32),
        jnp.full((slots,), -1, jnp.int32)).as_text()
    return which(pf), which(dec)


def run_serving(cfg, seed: int = 0, slots: int = 4, max_len: int = 1024,
                prompt_lens: Sequence[int] = (64, 200, 480),
                requests: int = 8, new_tokens: int = 32,
                tol: float = LOGIT_TOL, log: Log = say) -> None:
    """Serve ``requests`` random prompts and check every token; raises on
    the first failed check."""
    t0 = time.perf_counter()
    params, _ = split_leaves(M.init_model(jax.random.PRNGKey(seed), cfg))
    params = jax.block_until_ready(params)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    log(f"  {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{n_params} params ({cfg.dtype}); init "
        f"{time.perf_counter() - t0:.1f} s")
    engine = ServeEngine(cfg, params, batch_slots=slots, max_len=max_len)
    prefill_attn, decode_attn = attention_paths(engine, prompt_lens[0])
    log(f"  attention: prefill on {prefill_attn}, decode on {decode_attn} "
        f"(the cached engine passes traced offsets, which "
        f"kernels.ops.attention sends to XLA)")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=prompt_lens[i % len(prompt_lens)]
                            ).astype(np.int32) for i in range(requests)]
    t0 = time.perf_counter()
    rids = [engine.submit(p, max_new_tokens=new_tokens) for p in prompts]
    results = engine.run()
    log(f"  served {requests} requests x {new_tokens} tokens, prompt "
        f"lengths {sorted(set(prompt_lens))}, {slots} slots, max_len "
        f"{max_len}, chunk {engine.chunk_size}: "
        f"{time.perf_counter() - t0:.1f} s with compiles; {engine.stats}")
    worst, shares = 0.0, []
    t0 = time.perf_counter()
    for prompt, rid in zip(prompts, rids):
        gen = results[rid]
        if len(gen) != new_tokens:
            raise AssertionError(f"request {rid}: {len(gen)} tokens, "
                                 f"expected {new_tokens}")
        gap, share = reference_check(cfg, params, prompt, gen, tol)
        worst = max(worst, gap)
        shares.append(share)
    log(f"  reference (float32, highest precision, kernel_impl=ref): every "
        f"token within {tol} of the max logit (worst gap {worst:.4f}); "
        f"exact-argmax share {float(np.mean(shares)):.4f}; "
        f"{time.perf_counter() - t0:.1f} s")


# ------------------------------------------------------------ four chips
MULTI_BACKENDS = ("shardmap-csp", "shardmap-csp[comm=onesided]",
                  "pallas-fused[comm=onesided]")


def run_multichip(devices: Sequence, width: int = 128,
                  fused_width: int = 56, height: int = 1000,
                  fused_height: int = 100, expect_interpret: bool = False,
                  scaling_ranks: Tuple[int, ...] = (1, 2, 4),
                  log: Log = say) -> List[str]:
    """The rank backends over a mesh of ``devices`` at ``len(devices)``
    times the one-chip widths, checked against the oracle and against
    ``xla-scan`` on ``devices[0]``; then the in-process weak-scaling
    sweep.  Returns the failed case names.

    ``pallas-fused[comm=onesided]`` holds one DMA semaphore pair per
    timestep and ring offset, and a v5e core has 2 KiB of them: with two
    offsets H=128 is refused, so it runs at ``fused_height``.
    """
    n = len(devices)
    oracle = functools.lru_cache(maxsize=None)(execute_reference)
    failures = []
    for pattern, kw in PATTERNS[:2]:
        for spec in MULTI_BACKENDS:
            fused = spec.startswith("pallas")
            w = n * (fused_width if fused else width)
            h = fused_height if fused else height
            g = make_graph(w, h, pattern, "compute", **kw)
            name = (f"{spec} {pattern}{kw.get('radix', '')}/compute W={w} "
                    f"H={h} on {n} chips")
            try:
                be = get_backend(spec, devices=devices)
                if fused:
                    check_fused(be, [g], expect_interpret)
                t0 = time.perf_counter()
                out = be.run([g])[0]
                run_s = time.perf_counter() - t0
                check_outputs(g, out, expected=oracle(g))
                with jax.default_device(devices[0]):
                    one = get_backend("xla-scan").run([g])[0]
                check_outputs(g, one, expected=oracle(g))
                if not (np.asarray(out)[:, :4] == np.asarray(one)[:, :4]).all():
                    raise AssertionError("checksum slots differ from "
                                         "xla-scan on one chip")
                log(f"  ok    {name}: compile+run {run_s:.1f} s")
            except Exception:
                failures.append(name)
                log(f"  FAIL  {name}\n{traceback.format_exc()}")
    spec = ScalingSpec(name="metg_scaling.chip_smoke", backend="shardmap-csp",
                       ranks=scaling_ranks)
    try:
        res = run_scaling(spec, smoke=True, devices=list(devices))
        for c in res.cells:
            if c["devices"] != c["ranks"]:
                raise AssertionError(f"rank cell {c['ranks']} ran on "
                                     f"{c['devices']} devices")
            log(f"  ok    weak scaling r={c['ranks']} W={c['width']}: "
                f"{c['devices']} device(s), elapsed {c['elapsed_s']:.6f} s, "
                f"weak efficiency {c['weak_efficiency']:.3f}")
    except Exception:
        failures.append("weak scaling")
        log(f"  FAIL  weak scaling\n{traceback.format_exc()}")
    return failures


# ------------------------------------------------------------------ main
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the multi-rank backends, on four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX finds {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    dev = devices[0]
    say(f"[bring-up] {len(devices)} x {dev.device_kind} ({dev.platform}); "
        f"compile cache {cache}; times below are bring-up observations, "
        f"not metrics")
    failed = []
    t_all = time.perf_counter()
    if args.chips == 4:
        say("[bring-up] phase: multi-rank backends on 4 chips")
        failed += run_multichip(devices[:4])
    else:
        with jax.default_device(dev):
            say("[bring-up] phase: Task Bench on 1 chip")
            t0 = time.perf_counter()
            failed += run_taskbench(TaskBenchSizes())
            say(f"[bring-up] Task Bench phase: "
                f"{time.perf_counter() - t0:.1f} s")
            say("[bring-up] phase: qwen1.5-0.5b serving on 1 chip")
            t0 = time.perf_counter()
            try:
                run_serving(get_config("qwen1.5-0.5b"))
            except Exception:
                failed.append("qwen1.5-0.5b serving")
                say(f"  FAIL  serving\n{traceback.format_exc()}")
            say(f"[bring-up] serving phase: {time.perf_counter() - t0:.1f} s")
    say(f"[bring-up] total {time.perf_counter() - t_all:.1f} s")
    if failed:
        print(f"chip_smoke: {len(failed)} check(s) failed: {failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
