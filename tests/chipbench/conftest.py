"""A benchmark tree of its own for each test: ``BENCHMARK.json``, the
real metric readers, and tiny cells that run on the CPU."""
import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# Configurations that no cell of the benchmark runs yet, as changes to
# ``stencil-compute``: the paper's nearest pattern with radix 5.
DERIVED = {"nearest5-compute": {"name": "nearest5-compute",
                                "pattern": "nearest",
                                "pattern_params": {"radix": 5},
                                "dep_offsets": [-2, -1, 0, 1, 2],
                                "radix": 5}}


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def tiny_root(tmp_path):
    """``make(backend, width=8, height=16, iterations=(16, 4, 1), chips=1,
    config="stencil-compute")`` writes a cell named "tiny" into a copy of
    the benchmark's tree and returns the tree's root."""
    root = str(tmp_path / "bench")
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def make(backend, width=8, height=16, iterations=(16, 4, 1), chips=1,
             config="stencil-compute"):
        cfg_path = os.path.join(root, "chipbench", "configs", config + ".json")
        base = "stencil-compute" if config in DERIVED else config
        with open(os.path.join(root, "chipbench", "configs",
                               base + ".json")) as f:
            cfg = json.load(f)
        cfg.update(DERIVED.get(config, {}))
        cfg["height"] = height
        write_json(cfg_path, cfg)
        if config not in [c["name"] for c in bench["configs"]]:
            bench["configs"].append({
                "name": config, "source": cfg["source"], "reduced": [],
                "file": f"chipbench/configs/{config}.json", "why": "tiny"})
        bench["workloads"] = [{"name": "tiny", "config": config,
                               "traffic": "tiny", "chips": chips,
                               "why": "a size the CPU runs in seconds"}]
        write_json(os.path.join(root, "BENCHMARK.json"), bench)
        write_json(os.path.join(root, "chipbench", "cells", "tiny.json"),
                   {"config": config, "traffic": "tiny", "chips": chips,
                    "backend": backend, "width": width,
                    "iterations": list(iterations), "why": "tiny"})
        return root

    return make
