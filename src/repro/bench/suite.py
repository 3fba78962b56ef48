"""Declarative benchmark campaigns: a TOML suite of bench families.

The scenario families (``benchmarks/bench_*.py``) have so far been run
one module at a time; a paper-style campaign is the cross product —
families x backends x repeats — plus bookkeeping (artifact collection,
table aggregation, the baseline gate).  This module makes the campaign a
*document* instead of a shell history (TaPS-style):

.. code-block:: toml

    name = "paper"
    parallel = 4          # concurrent cells (subprocesses)
    timer = "synthetic"   # suite default; cells may override

    [[tasks]]
    family = "bench_metg_patterns"
    backends = ["xla-scan", "shardmap-csp"]   # optional --backends filter
    rollouts = 2                              # repeat runs; byte-compared

Execution model: every cell is one ``python -m benchmarks.run --only
<family>`` subprocess — exactly the serial CLI, so a suite run writes
the *same* ``BENCH_*.json`` artifacts a serial run would (bit-identical
on the synthetic timer; asserted for rollouts).  ``parallel = N`` runs
up to N cells concurrently; artifact filenames are disjoint because one
family's scenarios share its name prefix and duplicate families are
rejected at validation time.  A failed cell fails the suite, but every
other cell still runs to completion (the failure names the cell).

``rollouts = k`` re-runs a cell ``k - 1`` extra times into
``<out>/rollouts/<family>.rN/`` and byte-compares each rollout's
artifacts against the primary run's — on the deterministic synthetic
timer any difference is a real nondeterminism bug (unseeded RNG, dict
ordering, clock leakage), so a mismatch fails the suite.  Wall-clock
rollouts are kept for inspection but not compared (timing noise is not
a bug).

``benchmarks/suite.py`` is the CLI wrapper: TOML in, artifacts +
baseline gate + EXPERIMENTS.md tables out.
"""
from __future__ import annotations

import filecmp
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

try:  # py >= 3.11
    import tomllib
except ImportError:  # the container's 3.10: same API, vendored package
    import tomli as tomllib

TIMERS = ("synthetic", "wallclock")


@dataclass(frozen=True)
class SuiteCell:
    """One campaign cell: a bench family plus its run knobs."""

    family: str
    backends: Optional[Tuple[str, ...]] = None  # None -> module defaults
    rollouts: int = 1
    timer: Optional[str] = None  # None -> suite default

    def __post_init__(self):
        if not self.family:
            raise ValueError("suite cell needs a family (bench module name)")
        if self.rollouts < 1:
            raise ValueError(
                f"cell {self.family!r}: rollouts must be >= 1, "
                f"got {self.rollouts}")
        if self.timer is not None and self.timer not in TIMERS:
            raise ValueError(
                f"cell {self.family!r}: unknown timer {self.timer!r}; "
                f"known: {TIMERS}")
        if self.backends is not None and not self.backends:
            raise ValueError(
                f"cell {self.family!r}: backends = [] would filter every "
                f"backend out; omit the key to run the module's defaults")


@dataclass(frozen=True)
class Suite:
    """A parsed campaign: named, bounded concurrency, ordered cells."""

    name: str
    cells: Tuple[SuiteCell, ...]
    parallel: int = 1
    timer: str = "synthetic"

    def __post_init__(self):
        if not self.name:
            raise ValueError("suite needs a name")
        if self.parallel < 1:
            raise ValueError(f"parallel must be >= 1, got {self.parallel}")
        if self.timer not in TIMERS:
            raise ValueError(
                f"unknown suite timer {self.timer!r}; known: {TIMERS}")
        if not self.cells:
            raise ValueError("suite has no [[tasks]] cells")

    def cell_timer(self, cell: SuiteCell) -> str:
        return cell.timer or self.timer


def parse_suite(text: str, source: str = "<suite>") -> Suite:
    """Parse TOML into a ``Suite``; structural errors name ``source``."""
    try:
        doc = tomllib.loads(text)
    except tomllib.TOMLDecodeError as e:
        raise ValueError(f"{source}: not valid TOML: {e}")
    known_top = {"name", "parallel", "timer", "tasks"}
    unknown = sorted(set(doc) - known_top)
    if unknown:
        raise ValueError(
            f"{source}: unknown top-level key(s) {unknown}; "
            f"known: {sorted(known_top)}")
    tasks = doc.get("tasks")
    if not isinstance(tasks, list) or not tasks:
        raise ValueError(f"{source}: needs at least one [[tasks]] cell")
    known_cell = {"family", "backends", "rollouts", "timer"}
    cells = []
    for i, t in enumerate(tasks):
        if not isinstance(t, dict):
            raise ValueError(f"{source}: [[tasks]] entry #{i + 1} is not "
                             f"a table")
        unknown = sorted(set(t) - known_cell)
        if unknown:
            raise ValueError(
                f"{source}: [[tasks]] entry #{i + 1} "
                f"({t.get('family', '?')!r}): unknown key(s) {unknown}; "
                f"known: {sorted(known_cell)}")
        backends = t.get("backends")
        if backends is not None:
            if (not isinstance(backends, list)
                    or any(not isinstance(b, str) for b in backends)):
                raise ValueError(
                    f"{source}: [[tasks]] entry #{i + 1} "
                    f"({t.get('family', '?')!r}): backends must be a list "
                    f"of spec strings")
            backends = tuple(backends)
        try:
            cells.append(SuiteCell(
                family=str(t.get("family", "")),
                backends=backends,
                rollouts=int(t.get("rollouts", 1)),
                timer=t.get("timer")))
        except ValueError as e:
            raise ValueError(f"{source}: [[tasks]] entry #{i + 1}: {e}")
    try:
        return Suite(name=str(doc.get("name", "")),
                     cells=tuple(cells),
                     parallel=int(doc.get("parallel", 1)),
                     timer=doc.get("timer", "synthetic"))
    except ValueError as e:
        raise ValueError(f"{source}: {e}")


def load_suite(path: str) -> Suite:
    with open(path, "rb") as f:
        text = f.read().decode("utf-8")
    return parse_suite(text, source=path)


def validate_suite(suite: Suite, known_families: Sequence[str],
                   known_backends: Optional[Sequence[str]] = None) -> None:
    """Reject cells naming unknown families/backends (and duplicates).

    Runs before any subprocess is spawned: a typo'd family must exit
    nonzero *naming the entry*, never launch a partial campaign.
    Backend specs are checked by parsing (``auto[...]`` and option
    brackets are legal spec syntax, not registry keys); duplicate
    families are rejected because two cells of one family would race on
    the same ``BENCH_*.json`` filenames.
    """
    problems = []
    seen: Dict[str, int] = {}
    for i, cell in enumerate(suite.cells, 1):
        if cell.family not in known_families:
            problems.append(
                f"[[tasks]] entry #{i}: unknown family {cell.family!r}; "
                f"known: {', '.join(known_families)}")
            continue
        if cell.family in seen:
            problems.append(
                f"[[tasks]] entry #{i}: duplicate family {cell.family!r} "
                f"(already cell #{seen[cell.family]}; two cells of one "
                f"family would overwrite each other's artifacts)")
        seen.setdefault(cell.family, i)
        for b in cell.backends or ():
            try:
                from ..backends.base import parse_backend_spec

                base, _ = parse_backend_spec(b)
            except ValueError as e:
                problems.append(
                    f"[[tasks]] entry #{i} ({cell.family!r}): malformed "
                    f"backend spec {b!r}: {e}")
                continue
            if (known_backends is not None and base != "auto"
                    and base not in known_backends):
                problems.append(
                    f"[[tasks]] entry #{i} ({cell.family!r}): unknown "
                    f"backend {b!r}; known: "
                    f"{', '.join(known_backends)} (+ auto)")
    if problems:
        raise ValueError(
            f"suite {suite.name!r} failed validation:\n  "
            + "\n  ".join(problems))


@dataclass
class CellRun:
    """One executed cell (or rollout): its command and outcome."""

    cell: SuiteCell
    out_dir: str
    rollout: int  # 0 = primary run
    returncode: int
    stdout: str
    stderr: str

    @property
    def ok(self) -> bool:
        return self.returncode == 0

    @property
    def label(self) -> str:
        base = self.cell.family
        return base if self.rollout == 0 else f"{base}.r{self.rollout}"


@dataclass
class SuiteResult:
    """A completed campaign: every cell run + derived failure lists."""

    suite: Suite
    out_dir: str
    runs: List[CellRun] = field(default_factory=list)
    # (label, detail) pairs: cells that exited nonzero / rollouts whose
    # artifacts differed from the primary run's
    failures: List[Tuple[str, str]] = field(default_factory=list)
    mismatches: List[Tuple[str, str]] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.failures and not self.mismatches

    def summary(self) -> str:
        lines = []
        for r in self.runs:
            lines.append(f"{r.label}: {'ok' if r.ok else f'EXIT {r.returncode}'}")
        for label, detail in self.mismatches:
            lines.append(f"{label}: ROLLOUT MISMATCH {detail}")
        lines.append(
            f"suite {self.suite.name!r}: {len(self.runs)} cell run(s), "
            + ("all ok" if self.ok
               else f"{len(self.failures)} failure(s), "
                    f"{len(self.mismatches)} rollout mismatch(es)"))
        return "\n".join(lines)


def _repo_root() -> str:
    # src/repro/bench/suite.py -> repo checkout root
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def cell_command(suite: Suite, cell: SuiteCell, out_dir: str,
                 smoke: bool, python: str = sys.executable) -> List[str]:
    """The exact serial CLI a cell runs — one family of ``benchmarks.run``."""
    cmd = [python, "-m", "benchmarks.run",
           "--only", cell.family,
           "--artifacts", out_dir,
           "--timer", suite.cell_timer(cell)]
    if smoke:
        cmd.append("--smoke")
    if cell.backends:
        cmd += ["--backends", ",".join(cell.backends)]
    return cmd


def _run_cell(suite: Suite, cell: SuiteCell, out_dir: str, rollout: int,
              smoke: bool, python: str, cwd: str,
              env: Dict[str, str]) -> CellRun:
    os.makedirs(out_dir, exist_ok=True)
    proc = subprocess.run(
        cell_command(suite, cell, out_dir, smoke, python),
        capture_output=True, text=True, cwd=cwd, env=env)
    return CellRun(cell=cell, out_dir=out_dir, rollout=rollout,
                   returncode=proc.returncode,
                   stdout=proc.stdout, stderr=proc.stderr)


def rollout_dir(out_dir: str, cell: SuiteCell, rollout: int) -> str:
    return os.path.join(out_dir, "rollouts", f"{cell.family}.r{rollout}")


def _compare_rollout(primary_dir: str, rollout_run: CellRun,
                     ) -> List[Tuple[str, str]]:
    """Byte-compare a rollout's artifacts against the primary run's."""
    from .compare import bench_json_names

    mismatches = []
    names = bench_json_names(rollout_run.out_dir)
    if not names:
        mismatches.append((rollout_run.label,
                           "rollout wrote no BENCH_*.json artifacts"))
    for fname in names:
        primary = os.path.join(primary_dir, fname)
        current = os.path.join(rollout_run.out_dir, fname)
        if not os.path.exists(primary):
            mismatches.append(
                (rollout_run.label,
                 f"{fname} exists only in the rollout"))
        elif not filecmp.cmp(primary, current, shallow=False):
            mismatches.append(
                (rollout_run.label,
                 f"{fname} differs byte-wise from the primary run "
                 f"(nondeterminism on the deterministic timer)"))
    return mismatches


def run_suite(suite: Suite, out_dir: str, smoke: bool = False,
              python: str = sys.executable,
              cwd: Optional[str] = None,
              parallel: Optional[int] = None) -> SuiteResult:
    """Execute every cell (and its rollouts) and collect the outcome.

    Cells run as ``benchmarks.run`` subprocesses.  Synthetic-timer cells
    run at most ``parallel`` (default: the suite's ``parallel``) at a
    time; every other cell measures the device, which belongs to one
    process at a time, so those run afterwards, one by one, with nothing
    beside them.  A nonzero cell never cancels the others.  Rollout
    byte-comparison applies only to synthetic-timer cells.
    """
    cwd = cwd or _repo_root()
    env = dict(os.environ)
    src = os.path.join(cwd, "src")
    parts = [src] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                     if p and p != src]
    env["PYTHONPATH"] = os.pathsep.join(parts)
    out_dir = os.path.abspath(out_dir)

    jobs = []  # (cell, rollout, dir)
    for cell in suite.cells:
        jobs.append((cell, 0, out_dir))
        for r in range(1, cell.rollouts):
            jobs.append((cell, r, rollout_dir(out_dir, cell, r)))

    nworkers = parallel if parallel is not None else suite.parallel
    result = SuiteResult(suite=suite, out_dir=out_dir)
    synthetic = [j for j in jobs if suite.cell_timer(j[0]) == "synthetic"]
    with ThreadPoolExecutor(max_workers=max(1, nworkers)) as pool:
        futures = [pool.submit(_run_cell, suite, cell, d, r, smoke,
                               python, cwd, env)
                   for cell, r, d in synthetic]
        runs = [f.result() for f in futures]
    runs += [_run_cell(suite, cell, d, r, smoke, python, cwd, env)
             for cell, r, d in jobs if suite.cell_timer(cell) != "synthetic"]

    order = {(c.family, r): i for i, (c, r, _) in enumerate(jobs)}
    runs.sort(key=lambda cr: order[(cr.cell.family, cr.rollout)])
    result.runs = runs
    for cr in runs:
        if not cr.ok:
            tail = "\n".join((cr.stderr.strip() or cr.stdout.strip())
                             .splitlines()[-5:])
            result.failures.append((cr.label, tail))
    ok_primary = {cr.cell.family for cr in runs
                  if cr.rollout == 0 and cr.ok}
    for cr in runs:
        if (cr.rollout > 0 and cr.ok
                and cr.cell.family in ok_primary
                and suite.cell_timer(cr.cell) == "synthetic"):
            result.mismatches.extend(_compare_rollout(out_dir, cr))
    return result
