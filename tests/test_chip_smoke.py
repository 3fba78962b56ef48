"""chip_smoke.py's phases at a tiny size on the CPU, through the same checks.

The megakernel runs in interpret mode and qwen1.5-0.5b is ``reduced()``;
on the chip the same functions run at the published sizes.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke as CS
from repro.configs import get_config, reduced
from repro.models import model as M
from repro.models.layers import split_leaves

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = CS.TaskBenchSizes(width=8, height=6, host_height=4, fused_width=8,
                         fused_memory_width=8, fused_concurrent_width=8,
                         concurrent=2)


def test_taskbench_phase_tiny():
    lines = []
    failed = CS.run_taskbench(TINY, fused="pallas-fused[interpret=True]",
                              expect_interpret=True, log=lines.append)
    assert failed == [], "\n".join(lines)
    # every backend x pattern x kernel cell, plus the two concurrent runs
    assert len(lines) == 4 * 3 * 3 + 2


def test_fused_check_refuses_interpret_mode():
    """On the chip the fused backend must not be in interpret mode."""
    g = CS.make_graph(width=8, height=6)
    be = CS.get_backend("pallas-fused[interpret=True]")
    with pytest.raises(AssertionError, match="interpret=True"):
        CS.check_fused(be, [g], expect_interpret=False)


def test_serving_phase_tiny():
    lines = []
    CS.run_serving(reduced(get_config("qwen1.5-0.5b")), max_len=64,
                   prompt_lens=(5, 9, 12), requests=5, new_tokens=6,
                   slots=2, log=lines.append)
    assert any("exact-argmax share" in line for line in lines)
    assert any("prefill on xla, decode on xla" in line for line in lines)


def test_reference_check_rejects_wrong_tokens():
    """The serving check has teeth: tokens the reference ranks low fail."""
    cfg = reduced(get_config("qwen1.5-0.5b"))
    params, _ = split_leaves(M.init_model(jax.random.PRNGKey(0), cfg))
    prompt = np.arange(1, 9, dtype=np.int32)
    cfg_ref = dataclasses.replace(cfg, kernel_impl="ref", dtype="float32")
    logits = M.forward(params, cfg_ref, tokens=prompt[None])[0][0, -1]
    worst = [int(np.argmin(np.asarray(logits)))] * 4
    with pytest.raises(AssertionError, match="below the max"):
        CS.reference_check(cfg, params, prompt, worst, CS.LOGIT_TOL)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_tpu(argv, capsys):
    assert CS.main(argv) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_main_refuses_too_few_chips(monkeypatch, capsys):
    """``--chips 4`` on a host that shows one TPU runs nothing."""
    class OneTpu:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(CS.jax, "devices", lambda: [OneTpu()])
    assert CS.main(["--chips", "4"]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs 4 TPU chips, JAX finds 1" in out.err


def test_multichip_phase_on_four_cpu_devices():
    """The --chips 4 phase on four virtual CPU devices: the rank backends
    over a 4-device mesh and the in-process weak-scaling sweep."""
    code = (
        "import sys, jax, chip_smoke as CS\n"
        "lines = []\n"
        "failed = CS.run_multichip(jax.devices()[:4], width=16,\n"
        "    fused_width=8, height=12, fused_height=10,\n"
        "    expect_interpret=True, log=lines.append)\n"
        "print('\\n'.join(lines))\n"
        "assert failed == [], failed\n"
        "assert sum('weak scaling' in l for l in lines) == 3\n"
        "print('MULTIOK')\n")
    env = dict(os.environ, JAX_NUM_CPU_DEVICES="4", JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0 and "MULTIOK" in p.stdout, p.stdout + p.stderr
