"""Backend interface: a 'programming system' in the paper's sense.

Each backend executes a list of concurrent task graphs (paper: multiple
graphs model task parallelism) and returns the final-timestep payload of
each.  ``prepare`` returns a ``Runner``: a zero-arg callable that
re-executes the prepared workload and returns once the payloads are on the
host — the METG harness times that.
"""
from __future__ import annotations

import ast
import inspect
import re
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Type

import jax
import numpy as np

from ..core.graph import TaskGraph

_BACKENDS: Dict[str, Type["Backend"]] = {}

# "name[key=value,key2=value2]" — the declarative backend-spec string.
# ScenarioSpec.backend and the Timer protocol carry a single string, so
# constructor options (schedule="steal", comm_overlap=True, comm="a2a")
# must be expressible inside it.
_SPEC_RE = re.compile(r"^([A-Za-z0-9_.-]+)(?:\[(.*)\])?$")


def register_backend(name: str):
    def deco(cls):
        cls.name = name
        _BACKENDS[name] = cls
        return cls

    return deco


def backend_names() -> List[str]:
    return sorted(_BACKENDS)


def parse_backend_spec(spec: str) -> Tuple[str, Dict[str, object]]:
    """Split ``"name[key=value,...]"`` into (name, constructor kwargs).

    Values parse as Python literals (``True``, ``4``, ``1.5``); bare
    words fall back to strings, so ``host-dynamic[schedule=steal]`` works
    without quoting.  A bare ``"name"`` parses to ``(name, {})``.

    The returned kwargs are *canonicalized* — sorted by key — so two
    spec strings that differ only in option order parse identically and
    ``canonical_backend_spec`` renders them to the same string (option
    order must never make two identical scenarios compare as different
    in the ``--baseline`` gate).
    """
    m = _SPEC_RE.match(spec)
    if m is None:
        raise ValueError(
            f"malformed backend spec {spec!r}; expected "
            f"'name' or 'name[key=value,...]'")
    name, kwstr = m.group(1), m.group(2)
    kwargs: Dict[str, object] = {}
    if kwstr:
        for part in kwstr.split(","):
            part = part.strip()
            if "=" not in part:
                raise ValueError(
                    f"malformed backend option {part!r} in {spec!r}; "
                    f"expected key=value")
            k, v = (s.strip() for s in part.split("=", 1))
            if not k:
                raise ValueError(f"empty option name in backend spec {spec!r}")
            if k in kwargs:
                # a duplicate is always a typo'd spec — the last value
                # silently winning would hide it
                raise ValueError(
                    f"duplicate option {k!r} in backend spec {spec!r}")
            if v.lower() in ("true", "false"):
                # accept the JSON/YAML spellings too: a bare 'false'
                # falling through to the string branch would be truthy
                kwargs[k] = v.lower() == "true"
                continue
            try:
                kwargs[k] = ast.literal_eval(v)
            except (ValueError, SyntaxError):
                kwargs[k] = v  # bare word: a string (steal, a2a, ...)
    return name, dict(sorted(kwargs.items()))


def canonical_backend_spec(spec: str) -> str:
    """The canonical rendering of a backend spec string.

    Parses and re-renders with options sorted by key (bools/numbers in
    Python spelling, strings as bare words), so key-reordered spellings
    of the same spec — ``"x[a=1,b=2]"`` vs ``"x[b=2,a=1]"`` — map to one
    identity.  ``bench.compare`` compares scenario backends through this
    so a reordered baseline never reads as a vanished scenario.
    """
    name, kwargs = parse_backend_spec(spec)
    if not kwargs:
        return name
    opts = ",".join(f"{k}={v}" for k, v in kwargs.items())
    return f"{name}[{opts}]"


def backend_option_signature(name: str) -> Dict[str, object]:
    """The registered backend's constructor options and their defaults.

    Maps option name -> default value (``inspect.Parameter.empty`` for
    required options).  This is the *known-options metadata* the spec
    validator rejects typos against and the tuner
    (``repro.bench.tuner.enumerate_mode_space``) prunes the legal
    backend/mode space with — one source of truth, the constructor
    signature itself.  Returns ``None`` when the constructor takes open
    ``**kwargs`` (it validates its own options).
    """
    if name not in _BACKENDS:
        raise KeyError(f"unknown backend {name!r}; known: {backend_names()}")
    init = _BACKENDS[name].__init__
    if init is object.__init__:
        return {}
    params = inspect.signature(init).parameters
    if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()):
        return None
    return {n: p.default for n, p in params.items()
            if n != "self" and p.kind in (
                inspect.Parameter.POSITIONAL_OR_KEYWORD,
                inspect.Parameter.KEYWORD_ONLY)}


def _check_ctor_kwargs(cls: Type["Backend"], name: str, kwargs: Dict) -> None:
    """Reject unknown constructor options, naming backend and key.

    A typo'd option (``sched=steal`` for ``schedule``) must fail loudly,
    not no-op — and the raw ``TypeError`` from ``cls(**kwargs)`` would
    name the class, not the backend the spec string asked for.
    """
    if not kwargs:
        return
    sig = backend_option_signature(name)
    if sig is None:
        return  # the constructor validates its own open kwargs
    known = list(sig)
    for k in kwargs:
        if k not in known:
            raise ValueError(
                f"backend {name!r} does not accept option {k!r}; "
                f"known options: {known if known else 'none'}")


def get_backend(name: str, devices: Optional[Sequence] = None,
                **kwargs) -> "Backend":
    """Instantiate a backend from a name or spec string.

    Explicit keyword arguments override options embedded in the spec
    string: ``get_backend("shardmap-csp[comm=a2a]", comm="halo")`` builds
    a halo-mode backend.  ``devices`` places a multi-rank spec's ranks
    (``Backend.device_options``); a single-device spec runs on JAX's
    default device whatever it holds.
    """
    base, spec_kw = parse_backend_spec(name)
    if base not in _BACKENDS:
        raise KeyError(f"unknown backend {base!r}; known: {backend_names()}")
    cls = _BACKENDS[base]
    merged = {**spec_kw, **kwargs}
    if devices is not None:
        merged = {**cls.device_options(list(devices), merged), **merged}
    _check_ctor_kwargs(cls, base, merged)
    return cls(**merged)


# The host spans of one graph run, in this order on the calling thread.
# With no profiler session a span costs under a microsecond.
LAUNCH = "taskbench.runner.launch"
WAIT = "taskbench.runner.wait"
READBACK = "taskbench.runner.readback"


def to_host(outs) -> List[np.ndarray]:
    """Each of the device arrays ``outs`` copied to the host."""
    return [np.asarray(o) for o in outs]


@dataclass(frozen=True)
class Runner:
    """A prepared graph run: each call runs it again and returns each
    graph's final payload on the host, in three host spans
    (``jax.profiler.TraceAnnotation``) that follow one another:

    * ``taskbench.runner.launch``: ``launch()``, argument handling and the
      enqueue of the program (for host dispatch, the whole dispatch loop);
    * ``taskbench.runner.wait``: the host blocked until every output exists;
    * ``taskbench.runner.readback``: ``readback(outputs)``, the copy to the
      host and any slicing.
    """

    launch: Callable[[], Any]
    readback: Callable[[Any], List[np.ndarray]] = to_host

    def __call__(self) -> List[np.ndarray]:
        with jax.profiler.TraceAnnotation(LAUNCH):
            outs = self.launch()
        with jax.profiler.TraceAnnotation(WAIT):
            outs = jax.block_until_ready(outs)
        with jax.profiler.TraceAnnotation(READBACK):
            return self.readback(outs)


def in_turn(runners: Sequence[Runner]) -> Callable[[], List[np.ndarray]]:
    """Independent runs: each starts once the one before is read back."""
    if len(runners) == 1:
        return runners[0]
    return lambda: [out for r in runners for out in r()]


class Backend:
    """Executes task graphs. Subclasses implement ``prepare``.

    ``prepare`` runs the graphs *independently* (one program each, or one
    sequential program); ``prepare_many`` is the concurrent entry point for
    multi-graph scenarios (paper Fig 9d: task parallelism) — backends that
    can overlap graphs override it (stacked graph dimension on the
    vectorized backends, interleaved wavefronts on host/CSP), and the
    default falls back to ``prepare``.
    """

    name = "base"
    # paper Table 4 analogue, reported by benchmarks:
    paradigm = ""
    # deterministic-model hints consumed by bench.timers.SyntheticTimer:
    # how this backend lays a wavefront's tasks over workers
    # (core.schedule policy), and whether it issues the next step's
    # communication ahead of the current kernel body (double buffering)
    sched_policy = "static"
    comm_overlap = False
    # which dispatch-cost model this backend's execution implies:
    # "per-task" — every task pays the runtime's dispatch overhead (the
    # paper's model, and XLA's per-op reality); "per-launch" — one fixed
    # launch cost for the whole graph batch (the fused megakernel).
    # Resolved leniently by name (bench.timers.backend_dispatch_model),
    # never by instantiation, so the default synthetic configuration
    # stays backend-free.
    dispatch_model = "per-task"

    @classmethod
    def device_options(cls, devices: List, options: Dict) -> Dict:
        """Constructor options that run the spec ``options`` over
        ``devices`` (``get_backend(..., devices=)``): none here, the
        backend runs on one device."""
        return {}

    def prepare(self, graphs: Sequence[TaskGraph]) -> Callable[[], List[np.ndarray]]:
        """Compile/stage the workload.  The returned callable is a
        ``Runner`` (or ``in_turn`` of one per program): its launch, wait
        and readback phases each run in a host span of their own, so a
        trace tells the host's launch, its wait on the chip and the copy
        back apart.  A new backend builds a ``Runner``, not a runner of
        its own."""
        raise NotImplementedError

    def prepare_many(self, graphs: Sequence[TaskGraph]) -> Callable[[], List[np.ndarray]]:
        """Stage ``graphs`` for *concurrent* execution (default: ``prepare``)."""
        return self.prepare(graphs)

    def run(self, graphs: Sequence[TaskGraph]) -> List[np.ndarray]:
        return self.prepare(graphs)()

    def run_many(self, graphs: Sequence[TaskGraph]) -> List[np.ndarray]:
        """Execute ``graphs`` concurrently; per-graph outputs, same order."""
        return self.prepare_many(graphs)()

    def lowered_hlo(self, graphs: Sequence[TaskGraph]) -> List[str]:
        """Optimized HLO of the compiled program(s) ``run_many`` executes.

        Empty when the backend has no whole-graph program (host dispatch).
        The dry-run timer feeds these to ``launch.roofline.analyze_hlo``.
        """
        return []


class StackedProgramBackend(Backend):
    """Shared scaffolding for single-device whole-program backends.

    Subclasses provide ``_build(graphs) -> (jitted_fn, *args)`` (one
    program, per-graph outputs) and ``_build_stacked(graphs) ->
    (jitted_fn, *args) | None`` (one program over a leading graph axis,
    when the graphs can share a task body); everything else — AOT
    compilation, runners, the concurrent fallback, HLO/StableHLO
    exposure — lives here so the scan, dataflow and megakernel backends
    cannot drift apart.
    """

    def _build(self, graphs: Sequence[TaskGraph]):
        raise NotImplementedError

    def _build_stacked(self, graphs: Sequence[TaskGraph]):
        return None  # no stacked form: prepare_many falls back to prepare

    def _compile(self, graphs: Sequence[TaskGraph]):
        fn, *args = self._build(graphs)
        return (fn.lower(*args).compile(), *args)

    def _compile_stacked(self, graphs: Sequence[TaskGraph]):
        built = self._build_stacked(graphs)
        if built is None:
            return None
        fn, *args = built
        return (fn.lower(*args).compile(), *args)

    def prepare(self, graphs: Sequence[TaskGraph]) -> Runner:
        """One compiled program for all ``graphs``, run as a ``Runner``
        (launch, wait, readback spans)."""
        compiled, *args = self._compile(graphs)
        return Runner(lambda: compiled(*args))

    def prepare_many(self, graphs: Sequence[TaskGraph]) -> Runner:
        graphs = list(graphs)
        built = self._compile_stacked(graphs)
        if built is None:
            return self.prepare(graphs)
        compiled, *args = built

        def readback(stacked) -> List[np.ndarray]:
            out = np.asarray(stacked)
            return [out[k] for k in range(out.shape[0])]

        return Runner(lambda: compiled(*args), readback)

    def lowered_hlo(self, graphs: Sequence[TaskGraph]) -> List[str]:
        graphs = list(graphs)
        built = self._compile_stacked(graphs)
        if built is not None:
            return [built[0].as_text()]
        return [self._compile(graphs)[0].as_text()]

    def lowered_stablehlo(self, graphs: Sequence[TaskGraph],
                          platforms: Sequence[str] = ("tpu",)) -> str:
        """Pre-optimization StableHLO of the concurrent program,
        cross-lowered for ``platforms`` (no such hardware needed — jax
        lowers for TPU on a CPU-only host).

        Unlike ``lowered_hlo`` (optimized HLO of the program *compiled
        for the host platform*), this exposes the structural form the
        fusion tests count kernel launches in: ``tpu_custom_call`` sites
        (one per Pallas launch) and ``stablehlo.while`` loops (one per
        ``lax.scan`` dispatch loop).
        """
        graphs = list(graphs)
        built = self._build_stacked(graphs)
        if built is None:
            built = self._build(graphs)
        fn, *args = built
        return fn.trace(*args).lower(
            lowering_platforms=tuple(platforms)).as_text()
