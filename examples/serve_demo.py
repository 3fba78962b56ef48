"""Serve a small model with continuous batching (slot-granular admission).

Five requests share three persistent batch slots: each request prefills
unpadded at batch 1 the moment a slot frees up (mid-decode for everyone
else) and decodes in on-device chunks — the host syncs once per chunk,
not once per token.  The engine stats printed at the end show the sync
arithmetic; rerun with ``decode_mode="host"`` to see the per-token
baseline pay one round-trip per generated token.

Run: PYTHONPATH=src python examples/serve_demo.py
"""
import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.models import model as M
from repro.models.layers import split_leaves
from repro.serve.engine import ServeEngine


def main():
    cfg = reduced(get_config("mixtral-8x7b"))  # MoE family, ring KV cache
    params, _ = split_leaves(M.init_model(jax.random.PRNGKey(0), cfg))
    engine = ServeEngine(cfg, params, batch_slots=3, max_len=128,
                         chunk_size=4, decode_mode="chunked")

    rng = np.random.RandomState(0)
    rids = [engine.submit(rng.randint(1, cfg.vocab_size, size=n),
                          max_new_tokens=m)
            for n, m in [(5, 8), (3, 4), (9, 6), (2, 10), (7, 5)]]
    # eos early-stop: this request halts as soon as it emits token 7
    rids.append(engine.submit(rng.randint(1, cfg.vocab_size, size=4),
                              max_new_tokens=12, eos_id=7))
    print(f"submitted {len(rids)} requests into {engine.slots} batch slots")
    out = engine.run()
    for rid in rids:
        print(f"  request {rid}: {len(out[rid])} tokens -> {out[rid]}")
    s = engine.stats
    print(f"stats: {s['prefills']} prefills, {s['decode_steps']} decode "
          f"steps in {s['chunk_launches']} chunk launches, "
          f"{s['host_syncs']} host syncs for {s['tokens_generated']} tokens")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
