"""``get_backend("auto")``: table-driven backend selection.

The paper's core finding is that no single runtime wins everywhere —
the fastest system flips with task granularity, dependence pattern,
payload size and node count (§V).  This backend closes the loop: at
dispatch time it reduces the workload to its tuning key
(``repro.bench.tuner.graphs_cutout``), looks the key up in the committed
tuning table (``benchmarks/tuning/TUNE_default.json``, regenerated with
``python -m benchmarks.run --tune``), and delegates every ``prepare`` /
``prepare_many`` / ``lowered_hlo`` call to the winning backend.

Resolution is a pure table lookup — **zero per-dispatch measurement** —
with deterministic nearest-key semantics on a miss (exact key, then
nearest bucket within the same graph shape, then nearest same-pattern
key; see ``TuningTable.resolve_entry``) and a documented fallback
(``tuner.DEFAULT_FALLBACK``) when the table has never seen the pattern
or there is no table at all.  Because execution is pure delegation,
``auto`` is bit-exact with whatever backend it resolves to and joins
the conformance matrix like any other backend.

Options (the ``auto[key=value]`` spec grammar):

``table=<path>``
    An explicit ``TUNE_*.json`` to consult.  Must exist and validate —
    pointing at a missing/corrupt table is a configuration error, not a
    silent fallback.  Default: the committed repo table (absent is fine;
    every dispatch then uses the fallback).
``timer=<name>``
    Which timer the consulted table must have been tuned on (default
    ``synthetic``).  A mismatched table is refused — wall-clock winners
    and fake-clock winners are different claims.
``fallback=<spec>``
    What a table miss dispatches (default ``xla-scan`` — the vectorized
    backend that runs every pattern with no mode prerequisites).

``get_backend("auto", devices=...)`` names the devices: the table is
read at their count and a multi-rank winner runs over them.  Without
them, every visible device.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..core.graph import TaskGraph
from .base import (Backend, backend_names, get_backend, parse_backend_spec,
                   register_backend)


@register_backend("auto")
class AutoBackend(Backend):
    """Delegates execution to the tuning table's winner for the workload.

    The planner in front of the paper's 'n systems': holds no execution
    machinery of its own, so conformance is delegation-exact by
    construction.
    """

    paradigm = "self-tuning planner (table-driven dispatch)"

    def __init__(self, table: Optional[str] = None,
                 fallback: Optional[str] = None,
                 timer: str = "synthetic",
                 devices: Optional[Sequence] = None):
        from ..bench.tuner import DEFAULT_FALLBACK, load_tuning_table

        if fallback is None:
            fallback = DEFAULT_FALLBACK
        base, _ = parse_backend_spec(fallback)
        if base == "auto":
            raise ValueError("backend 'auto' cannot fall back to itself")
        if base not in backend_names():
            raise ValueError(
                f"auto fallback names unknown backend {base!r}; "
                f"known: {backend_names()}")
        self.fallback = fallback
        self.timer = timer
        # eager load: an explicit table= that is missing or corrupt is a
        # configuration error and must fail at get_backend() time, not
        # on the first dispatch
        self.table = load_tuning_table(table)
        if self.table is not None and self.table.timer != timer:
            raise ValueError(
                f"tuning table {self.table.path or '<default>'} was tuned "
                f"on timer {self.table.timer!r} but auto asked for "
                f"timer={timer!r}; retune with `benchmarks.run --tune "
                f"--timer {timer}` or point table= at a matching table")
        self.devices = list(devices) if devices is not None else None
        self._delegates: Dict[str, Backend] = {}

    @classmethod
    def device_options(cls, devices, options):
        return {"devices": devices}

    # -- resolution (pure lookup, nothing measured) ----------------------
    def _devices(self) -> List:
        if self.devices is None:
            import jax

            self.devices = jax.devices()
        return self.devices

    def resolve_spec(self, graphs: Sequence[TaskGraph]) -> str:
        """The concrete backend spec this workload dispatches to."""
        from ..bench.tuner import graphs_cutout

        if self.table is None:
            return self.fallback
        winner = self.table.resolve(
            graphs_cutout(graphs, ndev=len(self._devices())))
        return winner if winner is not None else self.fallback

    def delegate(self, graphs: Sequence[TaskGraph]) -> Backend:
        """The (cached) backend instance the workload resolves to."""
        spec = self.resolve_spec(graphs)
        if spec not in self._delegates:
            self._delegates[spec] = get_backend(spec, devices=self._devices())
        return self._delegates[spec]

    # -- execution: pure delegation --------------------------------------
    def prepare(self, graphs: Sequence[TaskGraph]
                ) -> Callable[[], List[np.ndarray]]:
        return self.delegate(graphs).prepare(graphs)

    def prepare_many(self, graphs: Sequence[TaskGraph]
                     ) -> Callable[[], List[np.ndarray]]:
        return self.delegate(graphs).prepare_many(graphs)

    def lowered_hlo(self, graphs: Sequence[TaskGraph]) -> List[str]:
        return self.delegate(graphs).lowered_hlo(graphs)
