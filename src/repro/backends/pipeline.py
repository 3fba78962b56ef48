"""Pipeline backend: stages sharded over a ``stage`` mesh axis.

A pipeline schedule IS a sweep task graph (``dist.pipeline.pp_schedule``):
column = stage, timestep = clock tick, and the only cross-column
dependence reaches *left* — the activation arriving from the previous
stage.  This backend executes any such graph with one column block per
rank of a ``stage`` mesh axis and the activation moved stage-to-stage by
a one-directional ``ppermute`` ring (``CommPlan`` mode ``ring``) — the
point-to-point send a pipelined runtime would issue, with no reverse
link and no gather.

Because the comm-planning layer is shared, the backend is not limited to
sweeps: graphs whose deps also reach right fall back to the plan's
``halo`` exchange, and wide patterns (fft/spread/random) to
``allgather`` — so the backend joins the full benchmark matrix
(every pattern x every backend) unmodified.  Multi-graph scenarios
(``run_many``) inherit ``PlannedSPMDBackend``'s combined program: every
pipeline advances one clock tick per loop step, rings interleaved.

``comm_overlap=True`` (inherited from ``PlannedSPMDBackend``) switches
to the double-buffered program: the activation ring transfer for clock
tick t+1 is issued right after tick t's stage body — the async
stage-to-stage send a pipelined runtime posts while the next microbatch
computes.
"""
from __future__ import annotations

from .base import register_backend
from .csp import PlannedSPMDBackend

AXIS = "stage"


@register_backend("shardmap-pipeline")
class PipelineBackend(PlannedSPMDBackend):
    paradigm = "pipeline stages over a mesh axis (ppermute ring)"
    axis = AXIS
    prefer_ring = True
