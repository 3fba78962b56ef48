"""The ``moe_dispatch`` scenario: MoE dispatch comm volume as a roofline.

Not a task-graph scenario — the "graph" is one MoE layer's token dispatch
— but it is measured the same dry-run way as ``DryRunTimer``: lower the
compiled program, walk the optimized HLO with
``launch.roofline.analyze_hlo``, and report collective bytes and the
interconnect roofline term.  Two paths:

* **analytic** — per-rank a2a bytes from the same capacity math the kernel
  uses (``dist.collectives.dispatch_capacity``); pure host arithmetic, no
  devices, exact (verified against the compiled HLO in
  ``tests/test_distributed.py::test_moe_dispatch_roofline_8dev``).
* **compiled** — ``lowered_moe_hlo`` builds the mesh, lowers
  ``models.moe.apply_moe`` and feeds the optimized HLO to ``analyze_hlo``
  (needs ``data * model`` local devices).

The point of the scenario: SP-aware expert parallelism (``ep_mode="sp"``)
cuts per-plane dispatch volume by |model| versus token replication —
``report(spec_sp)["a2a_bytes"] * |model| == report(spec_rep)["a2a_bytes"]``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict

SCENARIO_NAME = "moe_dispatch"


@dataclass(frozen=True)
class MoEDispatchSpec:
    """One cell of the MoE dispatch measurement space."""

    arch: str = "mixtral-8x7b"
    batch: int = 8
    seq: int = 32
    data: int = 4            # EP group size (mesh `data` axis)
    model: int = 2           # TP/SP plane count (mesh `model` axis)
    ep_mode: str = "replicated"
    capacity_factor: float = 8.0
    dtype_bytes: int = 4     # activation dtype (f32 smoke default)

    @property
    def name(self) -> str:
        return f"{SCENARIO_NAME}.{self.arch}.{self.ep_mode}"

    def config(self):
        """The reduced arch config with this spec's MoE knobs applied."""
        from ..configs import get_config, reduced

        return dataclasses.replace(
            reduced(get_config(self.arch)),
            moe_capacity_factor=self.capacity_factor,
            ep_mode=self.ep_mode,
        )


def analytic_a2a_bytes(spec: MoEDispatchSpec) -> Dict[str, float]:
    """Per-(data, model)-rank dispatch+combine all-to-all bytes, from the
    exact capacity math ``models.moe._moe_a2a`` uses.  Token rows move as
    ``dtype_bytes``-wide activations plus one int32 expert id per row on
    the dispatch leg."""
    from ..launch.mesh import moe_dispatch_planes
    from ..models.moe import virtual_experts
    from ..dist.collectives import dispatch_capacity

    cfg = spec.config()
    _, _, sub = virtual_experts(cfg.num_experts, cfg.d_ff)
    # mirror the kernel's divisibility fallback (models.moe._moe_a2a /
    # dist.sharding): an sp request degrades to replicated when the
    # sequence does not shard over `model`, and the batch stays
    # unsharded when it does not divide `data` — otherwise this analytic
    # model would report SP-reduced volume the kernel never achieves
    eff_mode = spec.ep_mode
    if eff_mode == "sp" and spec.seq % spec.model:
        eff_mode = "replicated"
    planes = moe_dispatch_planes(
        {"data": spec.data, "model": spec.model}, eff_mode)
    # tokens per rank inside the MoE region: batch over `data`; seq over
    # `model` when SP-aware, replicated otherwise
    seq_shard = spec.model if eff_mode == "sp" else 1
    b_shard = spec.data if spec.batch % spec.data == 0 else 1
    n_loc = (spec.batch // b_shard) * (spec.seq // seq_shard)
    sends = n_loc * cfg.num_experts_per_tok * sub
    cap = dispatch_capacity(sends, spec.data, spec.capacity_factor)
    d = cfg.d_model
    rows = spec.data * cap
    dispatch = rows * (d * spec.dtype_bytes + 4)  # activations + expert ids
    combine = rows * d * spec.dtype_bytes
    return {
        "cap": float(cap),
        "rows_per_rank": float(rows),
        # 1.0 when the SP reduction is actually in effect (a spec with
        # seq % model != 0 runs — and is modelled — as replicated)
        "sp_effective": float(eff_mode == "sp"),
        "a2a_bytes": float(dispatch + combine),   # per plane, per layer
        "dispatch_planes": float(planes),         # identical a2a copies
        # volume summed over the |model| physical planes (sp planes move
        # distinct 1/|model| shards; replicated planes move |model| copies)
        "a2a_bytes_all_planes": float((dispatch + combine) * spec.model),
    }


def lowered_moe_hlo(spec: MoEDispatchSpec) -> str:
    """Optimized HLO of one compiled MoE layer on a (data, model) mesh.

    Needs ``spec.data * spec.model`` local devices (tests use the
    ``XLA_FLAGS`` subprocess harness).
    """
    import jax
    import jax.numpy as jnp

    from ..dist.sharding import make_rules, use_rules
    from ..launch.mesh import make_mesh
    from ..models import moe as MO
    from ..models.layers import split_leaves

    need = spec.data * spec.model
    if len(jax.devices()) < need:
        raise ValueError(
            f"moe_dispatch spec needs {need} devices "
            f"({spec.data}x{spec.model} mesh), have {len(jax.devices())}")
    cfg = spec.config()
    mesh = make_mesh((spec.data, spec.model), ("data", "model"))
    rules = make_rules(mesh)
    params, _ = split_leaves(MO.init_moe(jax.random.PRNGKey(0), cfg))
    x = jnp.zeros((spec.batch, spec.seq, cfg.d_model), jnp.float32)
    with mesh, use_rules(rules):
        compiled = jax.jit(
            lambda p, xx: MO.apply_moe(p, xx, cfg, impl="a2a")
        ).lower(params, x).compile()
    return compiled.as_text()


def moe_dispatch_report(spec: MoEDispatchSpec,
                        compiled: bool = False) -> Dict[str, float]:
    """The scenario's measurements: analytic a2a bytes (always) plus the
    compiled-HLO collective bytes and interconnect roofline seconds when
    ``compiled`` (requires enough local devices)."""
    from ..launch.roofline import LINK_BW

    out = dict(analytic_a2a_bytes(spec))
    out["a2a_roofline_s"] = out["a2a_bytes"] / LINK_BW
    if compiled:
        from ..launch.roofline import analyze_hlo

        colls = analyze_hlo(lowered_moe_hlo(spec))["collectives"]
        out["hlo_a2a_bytes"] = float(colls.get("all-to-all", 0.0))
        out["hlo_allgather_bytes"] = float(colls.get("all-gather", 0.0))
        out["hlo_collective_bytes"] = float(colls.get("total", 0.0))
    return out
