"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count is locked at first jax init, and
only launch/dryrun.py sets the 512-placeholder-device XLA flag).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def production_mesh_spec(
    *, multi_pod: bool = False, pipeline_stages: int = 1,
) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """(shape, axes) of the production mesh, without touching devices.

    Base: 16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).
    ``pipeline_stages > 1`` grows a trailing ``stage`` axis carved out of
    the data axis (total chip count is preserved), giving the 4D
    ``(pod, data, model, stage)`` strategy that ``dist.pipeline`` and the
    ``shardmap-pipeline`` backend shard over.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if pipeline_stages <= 1:
        return shape, axes
    data = shape[-2]
    if data % pipeline_stages:
        raise ValueError(
            f"data axis {data} not divisible by {pipeline_stages} stages")
    shape = shape[:-2] + (data // pipeline_stages, shape[-1], pipeline_stages)
    return shape, axes + ("stage",)


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """The one mesh constructor: ``shape`` over ``devices`` (default: the
    first ``prod(shape)`` visible devices), every axis ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to Explicit axes, which
    ``with_sharding_constraint`` (``dist.sharding.constrain``) refuses.
    """
    if devices is None:
        n = 1
        for s in shape:
            n *= int(s)
        devices = jax.devices()[:n]
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=list(devices))


def rank_mesh(axis: str) -> Mesh:
    """The 1-D mesh a rank backend uses when its caller passes none.

    Only on the CPU, where the caller fixed the device count before JAX
    started (``JAX_NUM_CPU_DEVICES``), so every visible device is one it
    asked for.  On an accelerator the devices are chips that another run
    may need, and the caller names them: this raises.
    """
    devices = jax.devices()
    if devices[0].platform != "cpu":
        raise ValueError(
            f"on {devices[0].platform} a rank backend needs mesh=: pass "
            f"make_mesh((n,), ({axis!r},), jax.devices()[:n])")
    return make_mesh((len(devices),), (axis,), devices)


def make_production_mesh(*, multi_pod: bool = False, pipeline_stages: int = 1):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips),
    optionally with a ``stage`` pipeline axis."""
    shape, axes = production_mesh_spec(
        multi_pod=multi_pod, pipeline_stages=pipeline_stages)
    return make_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many local devices tests spawned."""
    return make_mesh(shape, axes)


def moe_dispatch_planes(mesh_shape, ep_mode: str) -> int:
    """How many identical copies of the MoE dispatch all-to-all run
    concurrently over the ``model`` axis.

    ``replicated`` tokens duplicate the dispatch per model plane
    (|model| copies of the same a2a); SP-aware EP (``ep_mode="sp"``)
    shards the sequence over ``model`` so each plane moves distinct rows
    — one logical dispatch, per-plane volume cut by |model|.  Used by the
    ``moe_dispatch`` roofline scenario (``repro.bench.moe``) to model
    comm volume without devices.  ``mesh_shape`` is any axis-name ->
    size mapping (``Mesh.shape`` or a plain dict).
    """
    if ep_mode not in ("replicated", "sp"):
        raise ValueError(
            f"unknown ep_mode {ep_mode!r}; known: ('replicated', 'sp')")
    return 1 if ep_mode == "sp" else int(dict(mesh_shape).get("model", 1))
