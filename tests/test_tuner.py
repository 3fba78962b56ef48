"""Self-tuning backend planner: cutouts, mode space, table, auto dispatch.

Everything here runs on the deterministic fake clock (``SyntheticTimer``)
or pure table lookups, so the planner tests are exactly reproducible —
including the committed-table assertions that pin the paper's tentpole
claim (no single backend wins everywhere: the fused megakernel owns the
dispatch-bound cells, a one-sided SPMD spec owns the payload-bound ones).
"""
import json
import math
import os

import numpy as np
import pytest

from repro.backends import backend_names, get_backend
from repro.bench import (ScenarioSpec, SweepControls, SyntheticTimer,
                         TuningKey, TuningTable, auto_resolve,
                         build_tuning_table, diff_tuning_tables,
                         enumerate_mode_space, granularity_bucket,
                         graphs_cutout, load_tuning_table, payload_bucket,
                         read_tuning_json, spec_cutout,
                         validate_tuning_table, write_tuning_json)
from repro.bench.tuner import (DEFAULT_FALLBACK, backend_mode_specs,
                               default_table_path, key_slug, tuning_corpus,
                               tuning_table_path)
from repro.core import check_outputs, execute_reference, make_graph


# ------------------------------------------------------------------ buckets
def test_granularity_buckets_cover_the_axis():
    assert granularity_bucket(0) == "fine"
    assert granularity_bucket(15.9) == "fine"
    assert granularity_bucket(16) == "medium"
    assert granularity_bucket(255.9) == "medium"
    assert granularity_bucket(256) == "coarse"
    assert granularity_bucket(1e9) == "coarse"


def test_payload_buckets_cover_the_axis():
    assert payload_bucket(0) == "small"
    assert payload_bucket(1023) == "small"
    assert payload_bucket(1024) == "medium"
    assert payload_bucket(32767) == "medium"
    assert payload_bucket(32768) == "large"


def test_buckets_reject_garbage():
    with pytest.raises(ValueError):
        granularity_bucket(-1)
    with pytest.raises(ValueError):
        granularity_bucket(float("nan"))
    with pytest.raises(ValueError):
        granularity_bucket(float("inf"))
    with pytest.raises(ValueError):
        payload_bucket(-1)


def test_tuning_key_validates_eagerly():
    with pytest.raises(ValueError):
        TuningKey("stencil", "ultrafine", "small")
    with pytest.raises(ValueError):
        TuningKey("stencil", "fine", "huge")
    with pytest.raises(ValueError):
        TuningKey("", "fine", "small")
    with pytest.raises(ValueError):
        TuningKey("stencil", "fine", "small", ndev=0)
    assert key_slug(TuningKey("stencil", "fine", "small")) == \
        "stencil.fine.small.d1.g1"


# ------------------------------------------------------------------ cutouts
def test_graphs_cutout_reduces_a_workload_to_its_key():
    g = make_graph(width=4, height=6, pattern="stencil", iterations=64,
                   output_bytes=4096)
    assert graphs_cutout([g]) == TuningKey("stencil", "medium", "medium")
    assert graphs_cutout([g, g], ndev=8) == TuningKey(
        "stencil", "medium", "medium", ndev=8, ngraphs=2)
    with pytest.raises(ValueError):
        graphs_cutout([])


def test_spec_cutout_needs_a_single_point_sweep():
    spec = ScenarioSpec(name="cut", pattern="nearest", width=4, height=6,
                        output_bytes=16,
                        sweep=SweepControls(schedule=(64,)))
    assert spec_cutout(spec) == TuningKey("nearest", "medium", "small")
    multi = ScenarioSpec(name="cut2", pattern="nearest", width=4, height=6,
                         sweep=SweepControls(iterations_hi=64, n_points=3))
    with pytest.raises(ValueError, match="single-point"):
        spec_cutout(multi)


# --------------------------------------------------------------- mode space
def test_mode_space_prunes_illegal_combos():
    """Candidates come from each constructor's signature, with combos the
    constructor vetoes dropped — no hand-maintained legality table."""
    specs = enumerate_mode_space()
    assert "auto" not in {s.split("[")[0] for s in specs}
    # the megakernel accepts one-sided (its native in-kernel signaling)
    # but not the rendezvous comm modes
    pallas = backend_mode_specs("pallas-fused")
    assert pallas == ["pallas-fused", "pallas-fused[comm=onesided]"]
    # host dispatch sweeps its scheduling policy, nothing else
    assert backend_mode_specs("host-dynamic") == [
        "host-dynamic", "host-dynamic[schedule=steal]"]
    # SPMD backends sweep comm x overlap (schedule is not a ctor option)
    assert "shardmap-csp[comm=onesided,comm_overlap=True]" in specs
    # every candidate is canonical and instantiable
    from repro.backends.base import canonical_backend_spec

    for s in specs:
        assert canonical_backend_spec(s) == s
        get_backend(s)


def test_tuning_corpus_smoke_is_a_subset_of_the_full_grid():
    full = {c.key for c in tuning_corpus(smoke=False)}
    smoke = {c.key for c in tuning_corpus(smoke=True)}
    assert smoke < full


# ------------------------------------------------------- table build + files
def test_build_tuning_table_round_trips(tmp_path):
    doc = build_tuning_table(timer=SyntheticTimer(), smoke=True)
    path = write_tuning_json(doc, str(tmp_path))
    assert os.path.basename(path) == "TUNE_default.json"
    back = read_tuning_json(path)
    assert back == json.loads(json.dumps(doc))
    table = TuningTable(back, path=path)
    assert table.timer == "synthetic"
    # winner margins are measured against the next *distinct* candidate
    for e in back["entries"]:
        times = sorted(t for _, t in e["candidates"])
        assert e["elapsed_s"] == times[0]
        slower = [t for t in times if t > times[0]]
        if slower:
            assert e["margin"] == pytest.approx(
                (min(slower) - times[0]) / times[0])


def test_tuning_table_rejects_corruption(tmp_path):
    doc = json.loads(json.dumps(build_tuning_table(smoke=True)))
    validate_tuning_table(doc)

    def broken(mutate):
        bad = json.loads(json.dumps(doc))
        mutate(bad)
        with pytest.raises(ValueError):
            validate_tuning_table(bad)

    broken(lambda d: d.update(schema=99))
    broken(lambda d: d.update(kind="bench"))
    broken(lambda d: d.update(timer=""))
    broken(lambda d: d.update(entries=[]))
    broken(lambda d: d["entries"][0]["key"].update(flavor="spicy"))
    broken(lambda d: d["entries"][0]["key"].update(granularity="ultrafine"))
    broken(lambda d: d["entries"][0].update(margin=float("nan")))
    broken(lambda d: d["entries"][0].update(margin=-0.1))
    broken(lambda d: d["entries"][0].update(margin=True))  # bool != number
    broken(lambda d: d["entries"][0].update(elapsed_s=0.0))
    broken(lambda d: d["entries"][0].update(winner="no-such-backend spec"))
    broken(lambda d: d["entries"][0].update(winner="xla-scan[b=1,a=2]"))
    broken(lambda d: d["entries"][0].update(
        winner="host-dynamic[workers=99]"))  # parseable, not a candidate
    broken(lambda d: d["entries"].append(d["entries"][0]))  # duplicate key
    # truncated/garbage files raise ValueError naming the path
    stub = tmp_path / "TUNE_default.json"
    stub.write_text('{"schema": 1, "kind": "tuning_')
    with pytest.raises(ValueError, match="TUNE_default.json"):
        read_tuning_json(str(stub))


def test_load_tuning_table_explicit_path_must_exist(tmp_path):
    with pytest.raises(ValueError, match="not found"):
        load_tuning_table(str(tmp_path / "TUNE_nope.json"))


# --------------------------------------------------------------- resolution
def _mini_table():
    """A hand-built two-entry table exercising every resolution tier."""
    mk = lambda key, winner: {
        "key": key.to_dict(), "family": "metg", "winner": winner,
        "elapsed_s": 1e-3, "margin": 0.5,
        "candidates": [[winner, 1e-3], ["xla-scan", 2e-3]]}
    return TuningTable({
        "schema": 1, "kind": "tuning_table", "timer": "synthetic",
        "timer_config": {},
        "entries": [
            mk(TuningKey("stencil", "fine", "small"), "pallas-fused"),
            mk(TuningKey("stencil", "coarse", "large", ngraphs=4),
               "shardmap-csp[comm=onesided]"),
        ]})


def test_resolution_tiers_exact_then_bucket_then_shape():
    t = _mini_table()
    # tier 1: exact key
    assert t.resolve(TuningKey("stencil", "fine", "small")) == "pallas-fused"
    # tier 2: same (pattern, ndev, ngraphs), nearest bucket
    assert t.resolve(TuningKey("stencil", "medium", "small")) == \
        "pallas-fused"
    assert t.resolve(TuningKey("stencil", "coarse", "large")) == \
        "pallas-fused"  # ngraphs=1 keeps it in tier 2's g1 candidates
    # tier 3: same pattern only — nearest bucket, then nearest ngraphs
    assert t.resolve(TuningKey("stencil", "coarse", "large", ngraphs=3)) == \
        "shardmap-csp[comm=onesided]"
    assert t.resolve(TuningKey("stencil", "fine", "small", ndev=8)) == \
        "pallas-fused"
    # a pattern the table never saw is a miss, never a substitution
    assert t.resolve(TuningKey("trivial", "fine", "small")) is None
    assert t.entry(TuningKey("stencil", "medium", "small")) is None  # exact


def test_auto_resolve_spec_string_semantics(tmp_path):
    g = make_graph(width=4, height=6, pattern="stencil", iterations=1,
                   output_bytes=16)
    # non-auto specs pass straight through, whatever the table says
    assert auto_resolve("xla-static", [g]) == "xla-static"
    with pytest.raises(ValueError, match="known options"):
        auto_resolve("auto[grmbl=1]", [g])
    # explicit table= resolves from that table
    doc = build_tuning_table(smoke=True)
    path = write_tuning_json(doc, str(tmp_path))
    assert auto_resolve(f"auto[table={path}]", [g]) == "pallas-fused"
    # a pattern the table never tuned falls back (documented miss path)
    miss = make_graph(width=4, height=6, pattern="trivial", iterations=1)
    assert auto_resolve(f"auto[table={path}]", [miss]) == DEFAULT_FALLBACK
    assert auto_resolve(
        f"auto[fallback=host-dynamic,table={path}]", [miss]) == "host-dynamic"
    # a table tuned on another timer is refused, not silently trusted
    with pytest.raises(ValueError, match="timer"):
        auto_resolve(f"auto[table={path},timer=wallclock]", [g])


# ------------------------------------------------------- the committed table
def test_committed_table_pins_the_no_single_winner_claim():
    """The acceptance assertions: the fused megakernel owns the smallest
    granularity bucket (dispatch-bound, per-launch model undercuts every
    per-task runtime ~50x) and a one-sided comm spec owns the largest
    payload bucket (§V-F: rendezvous-free put/signal hides the wire)."""
    table = load_tuning_table(default_table_path())
    assert table.timer == "synthetic"
    fine = table.entry(TuningKey("stencil", "fine", "small"))
    assert fine is not None and fine["winner"] == "pallas-fused"
    assert fine["margin"] > 1.0  # not a squeaker: >2x over next-best
    big = table.entry(TuningKey("stencil", "medium", "large"))
    assert big is not None and "comm=onesided" in big["winner"]
    # every metg-family cell records the full legal mode space
    for key in table.keys():
        e = table.entry(key)
        if e["family"] == "metg":
            assert len(e["candidates"]) == len(enumerate_mode_space())


def test_diff_tuning_tables_gate_semantics():
    doc = build_tuning_table(smoke=True)
    fatal, notes = diff_tuning_tables(doc, doc)
    assert not fatal and not notes
    # changed winner at a shared key is fatal
    tampered = json.loads(json.dumps(doc))
    tampered["entries"][0]["winner"] = tampered["entries"][0]["candidates"][1][0]
    fatal, _ = diff_tuning_tables(doc, tampered)
    assert any("winner changed" in f for f in fatal)
    # a smoke regeneration against the full table: subset is notes-only
    full = build_tuning_table(smoke=False)
    fatal, notes = diff_tuning_tables(full, doc, subset_ok=True)
    assert not fatal and any("not retuned" in n for n in notes)
    fatal, _ = diff_tuning_tables(full, doc, subset_ok=False)
    assert any("missing" in f for f in fatal)
    # timer mismatch ends the comparison immediately
    wall = json.loads(json.dumps(doc))
    wall["timer"] = "wallclock"
    fatal, _ = diff_tuning_tables(wall, doc)
    assert any("timer changed" in f for f in fatal)


# ------------------------------------------------------------- auto backend
def test_auto_is_a_registered_backend_with_guarded_options(tmp_path):
    assert "auto" in backend_names()
    with pytest.raises(ValueError, match="cannot fall back to itself"):
        get_backend("auto[fallback=auto]")
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("auto[fallback=slurm]")
    # an explicit missing table fails at get_backend() time, not dispatch
    with pytest.raises(ValueError, match="not found"):
        get_backend(f"auto[table={tmp_path / 'TUNE_x.json'}]")


def test_auto_dispatch_is_bit_exact_with_its_resolved_backend():
    """The conformance cell: auto is pure delegation, so its outputs are
    bitwise identical to the backend the table resolves — the same
    invariant the cross-backend matrix asserts, one hop up."""
    be = get_backend("auto")
    for pattern, iters in (("stencil", 1), ("nearest", 2000)):
        g = make_graph(width=6, height=8, pattern=pattern, iterations=iters)
        spec = be.resolve_spec([g])
        assert spec != "auto"
        out = be.run([g])[0]
        check_outputs(g, out, expected=execute_reference(g))
        ref = get_backend(spec).run([g])[0]
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_auto_resolves_with_zero_per_dispatch_measurement():
    """Resolution must be a pure table lookup: no candidate backend is
    instantiated and nothing is timed on the resolve path."""
    be = get_backend("auto")
    g = make_graph(width=4, height=6, pattern="stencil", iterations=1)
    spec = be.resolve_spec([g])
    assert spec == "pallas-fused"
    assert be._delegates == {}  # resolve never built a backend
    be.delegate([g])
    assert list(be._delegates) == ["pallas-fused"]  # cached on execution


def _tpu_rank_mesh(axis):
    raise ValueError("on tpu a rank backend needs mesh=")


@pytest.mark.parametrize("winner", ["shardmap-csp[comm=onesided]",
                                    "pallas-fused[comm=onesided]"])
def test_rank_winners_run_where_rank_backends_need_devices(monkeypatch,
                                                           winner):
    """On a TPU a rank backend built without devices refuses to guess
    them (``launch.mesh.rank_mesh`` raises); auto and the wall clock name
    the devices, so a rank winner still runs."""
    from repro.backends import csp, megakernel
    from repro.bench.timers import WallClockTimer

    monkeypatch.setattr(csp, "rank_mesh", _tpu_rank_mesh)
    monkeypatch.setattr(megakernel, "rank_mesh", _tpu_rank_mesh)
    with pytest.raises(ValueError, match="needs mesh="):
        get_backend(winner)
    g = make_graph(width=6, height=8, pattern="stencil")
    be = get_backend("auto")
    monkeypatch.setattr(be, "resolve_spec", lambda graphs: winner)
    check_outputs(g, be.run([g])[0], expected=execute_reference(g))
    assert WallClockTimer(warmup=0, repeats=1).measure(winner, [g]) > 0
    assert winner in backend_mode_specs(winner.split("[")[0])


def test_synthetic_timer_charges_auto_as_its_resolved_backend():
    """The fake clock treats auto as the planner, not a cost model: an
    auto measurement equals the resolved winner's measurement exactly."""
    t = SyntheticTimer()
    g = make_graph(width=6, height=8, pattern="stencil", iterations=1,
                   output_bytes=16)
    resolved = auto_resolve("auto", [g])
    assert t.measure("auto", [g]) == t.measure(resolved, [g])
    # and the resolution is visible: at fine granularity the per-launch
    # model undercuts every per-task backend
    assert t.measure("auto", [g]) < t.measure("xla-scan", [g])


# ------------------------------------------------------------ the CLI paths
def test_run_only_rejects_unknown_modules(capsys):
    """Bugfix pin: a typo'd --only must exit nonzero naming the unknown
    entry and the registry — not silently run zero benchmarks."""
    from benchmarks.run import MODULES, main

    with pytest.raises(SystemExit) as exc:
        main(["--only", "bench_metg_pattens", "--artifacts", ""])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "bench_metg_pattens" in err
    assert "bench_metg_patterns" in err  # the registry is listed
    with pytest.raises(SystemExit) as exc:
        main(["--only", ",", "--artifacts", ""])
    assert exc.value.code == 2
    # a valid subset still runs (and prints its rows)


def test_run_only_valid_subset_still_runs(tmp_path, capsys):
    from benchmarks.run import main

    main(["--smoke", "--timer", "synthetic", "--only", "bench_peak",
          "--artifacts", str(tmp_path)])
    out = capsys.readouterr().out
    assert "bench_peak.elapsed" in out
    assert any(f.startswith("BENCH_") for f in os.listdir(tmp_path))


def test_tune_cli_round_trip_and_gate(tmp_path, capsys):
    from benchmarks.run import main

    art = tmp_path / "tune"
    main(["--tune", "--smoke", "--timer", "synthetic",
          "--artifacts", str(art)])
    out = capsys.readouterr().out
    assert "winner=pallas-fused" in out
    path = tuning_table_path(str(art))
    doc = read_tuning_json(path)
    # regenerating against itself passes; a directory baseline resolves
    main(["--tune", "--smoke", "--timer", "synthetic",
          "--artifacts", str(tmp_path / "tune2"),
          "--tune-baseline", str(art)])
    assert "winners match" in capsys.readouterr().out
    # a tampered committed winner trips the gate with exit 1
    doc["entries"][0]["winner"] = doc["entries"][0]["candidates"][-1][0]
    doc["entries"][0]["margin"] = 0.0
    with open(path, "w") as f:
        json.dump(doc, f)
    with pytest.raises(SystemExit) as exc:
        main(["--tune", "--smoke", "--timer", "synthetic",
              "--artifacts", str(tmp_path / "tune3"),
              "--tune-baseline", path])
    assert exc.value.code == 1
    assert "FATAL" in capsys.readouterr().out
    # --tune-baseline without --tune / --tune with --only are usage errors
    with pytest.raises(SystemExit) as exc:
        main(["--tune-baseline", path])
    assert exc.value.code == 2
    with pytest.raises(SystemExit) as exc:
        main(["--tune", "--only", "bench_peak"])
    assert exc.value.code == 2
