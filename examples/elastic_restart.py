"""Fault tolerance demo: crash mid-run, restart, verify bit-exact resume.

Run: PYTHONPATH=src python examples/elastic_restart.py
"""
import tempfile

import jax

from repro.configs import get_config, reduced
from repro.data.pipeline import DataConfig
from repro.train import train_step as TS
from repro.train.trainer import LoopConfig, Trainer


def main():
    cfg = reduced(get_config("yi-6b"))
    tcfg = TS.TrainConfig(base_lr=1e-3, warmup_steps=4, total_steps=60)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    d = tempfile.mkdtemp(prefix="repro_elastic_")
    loop = LoopConfig(num_steps=24, ckpt_dir=d, ckpt_every=8, log_every=0)

    ref = Trainer(cfg, tcfg, dcfg, loop)
    ref.run(jax.random.PRNGKey(0))
    ref_losses = {m["step"]: m["loss"] for m in ref.metrics_log}
    print(f"reference run: {len(ref_losses)} steps")

    d2 = tempfile.mkdtemp(prefix="repro_elastic_b_")
    loop2 = LoopConfig(num_steps=24, ckpt_dir=d2, ckpt_every=8, log_every=0)
    crashed = Trainer(cfg, tcfg, dcfg, loop2)
    try:
        crashed.run(jax.random.PRNGKey(0), fail_at=13)
    except RuntimeError as e:
        print(f"crash injected: {e}")

    resumed = Trainer(cfg, tcfg, dcfg, loop2)
    resumed.run(jax.random.PRNGKey(0))
    first = resumed.metrics_log[0]["step"]
    exact = all(m["loss"] == ref_losses[m["step"]]
                for m in resumed.metrics_log)
    print(f"resumed from checkpointed step {first} "
          f"(crash was at 13); losses bit-exact vs reference: {exact}")
    assert exact


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
