"""Declarative suite orchestrator (`repro.bench.suite` + CLI) and the
bench-CLI bugfix contracts that campaigns amplify:

* TOML parse/validate negative paths name the offending entry and exit
  nonzero before anything runs,
* a failed cell fails the suite but the remaining cells still complete,
* `parallel > 1` writes artifacts bit-identical to serial `run.py` runs
  (synthetic timer),
* every registry module's ``run`` accepts zero args,
* `--tables` never splices a partial artifact set, and corrupt-artifact
  skips are warned and counted.
"""
from __future__ import annotations

import inspect
import os

import pytest

from repro.bench.suite import (CellRun, Suite, SuiteCell, _compare_rollout,
                               cell_command, load_suite, parse_suite,
                               run_suite, validate_suite)

FAMILIES = ["bench_peak", "bench_metg_deps", "bench_metg_scaling"]


# ---------------------------------------------------------- parse errors
def test_parse_suite_rejects_bad_toml():
    with pytest.raises(ValueError, match="not valid TOML"):
        parse_suite("name = ", source="x.toml")
    with pytest.raises(ValueError, match="unknown top-level key"):
        parse_suite('name="s"\nparallell=2\n[[tasks]]\nfamily="bench_peak"')
    with pytest.raises(ValueError, match=r"\[\[tasks\]\] cell"):
        parse_suite('name="s"')
    with pytest.raises(ValueError, match="entry #2.*unknown key"):
        parse_suite('name="s"\n[[tasks]]\nfamily="bench_peak"\n'
                    '[[tasks]]\nfamily="bench_metg_deps"\nrolouts=2')
    with pytest.raises(ValueError, match="rollouts must be >= 1"):
        parse_suite('name="s"\n[[tasks]]\nfamily="bench_peak"\nrollouts=0')
    with pytest.raises(ValueError, match="unknown timer"):
        parse_suite('name="s"\n[[tasks]]\nfamily="bench_peak"\n'
                    'timer="cpu-cycles"')
    with pytest.raises(ValueError, match="backends must be a list"):
        parse_suite('name="s"\n[[tasks]]\nfamily="bench_peak"\n'
                    'backends="xla-scan"')
    with pytest.raises(ValueError, match=r"backends = \[\]"):
        parse_suite('name="s"\n[[tasks]]\nfamily="bench_peak"\n'
                    'backends=[]')
    with pytest.raises(ValueError, match="needs a name"):
        parse_suite('[[tasks]]\nfamily="bench_peak"')


def test_parse_suite_timer_inheritance():
    s = parse_suite('name="s"\ntimer="wallclock"\n'
                    '[[tasks]]\nfamily="bench_peak"\n'
                    '[[tasks]]\nfamily="bench_metg_deps"\n'
                    'timer="synthetic"')
    assert s.cell_timer(s.cells[0]) == "wallclock"
    assert s.cell_timer(s.cells[1]) == "synthetic"
    assert s.parallel == 1  # default


def test_validate_suite_names_the_entry():
    s = Suite(name="s", cells=(SuiteCell(family="bench_peak"),
                               SuiteCell(family="bench_typo")))
    with pytest.raises(ValueError, match="entry #2.*bench_typo"):
        validate_suite(s, known_families=FAMILIES)
    s = Suite(name="s", cells=(SuiteCell(family="bench_peak"),
                               SuiteCell(family="bench_peak")))
    with pytest.raises(ValueError, match="duplicate family"):
        validate_suite(s, known_families=FAMILIES)
    s = Suite(name="s", cells=(
        SuiteCell(family="bench_peak", backends=("not-a-backend",)),))
    with pytest.raises(ValueError, match="unknown backend 'not-a-backend'"):
        validate_suite(s, known_families=FAMILIES,
                       known_backends=["xla-scan"])
    # option brackets are spec syntax, not registry keys
    s = Suite(name="s", cells=(
        SuiteCell(family="bench_peak",
                  backends=("xla-scan", "auto[exclude=host-dynamic]")),))
    validate_suite(s, known_families=FAMILIES, known_backends=["xla-scan"])


def test_cell_command_is_the_serial_cli():
    suite = parse_suite('name="s"\ntimer="synthetic"\n'
                        '[[tasks]]\nfamily="bench_metg_scaling"\n'
                        'backends=["shardmap-csp", "auto"]')
    cmd = cell_command(suite, suite.cells[0], "/tmp/out", smoke=True,
                       python="PY")
    assert cmd == ["PY", "-m", "benchmarks.run",
                   "--only", "bench_metg_scaling",
                   "--artifacts", "/tmp/out",
                   "--timer", "synthetic", "--smoke",
                   "--backends", "shardmap-csp,auto"]


# ------------------------------------------------------- rollout compare
def test_compare_rollout_flags_byte_drift(tmp_path):
    primary, roll = tmp_path / "out", tmp_path / "out" / "r1"
    roll.mkdir(parents=True)
    (primary / "BENCH_x.a.json").write_text('{"v": 1}')
    (roll / "BENCH_x.a.json").write_text('{"v": 1}')
    run = CellRun(cell=SuiteCell(family="bench_peak"), out_dir=str(roll),
                  rollout=1, returncode=0, stdout="", stderr="")
    assert _compare_rollout(str(primary), run) == []
    (roll / "BENCH_x.a.json").write_text('{"v": 2}')
    bad = _compare_rollout(str(primary), run)
    assert len(bad) == 1 and "differs byte-wise" in bad[0][1]
    (roll / "BENCH_x.b.json").write_text("{}")
    assert any("only in the rollout" in d for _, d in
               _compare_rollout(str(primary), run))
    for f in roll.iterdir():
        f.unlink()
    assert any("no BENCH" in d for _, d in
               _compare_rollout(str(primary), run))


# --------------------------------------------------------- CLI + e2e runs
def test_suite_cli_exit2_on_validation(tmp_path, capsys):
    from benchmarks.suite import main

    bad = tmp_path / "bad.toml"
    bad.write_text('name="x"\n[[tasks]]\nfamily="bench_nope"\n')
    with pytest.raises(SystemExit) as exc:
        main([str(bad), "--smoke", "--artifacts", str(tmp_path / "out")])
    assert exc.value.code == 2
    assert "bench_nope" in capsys.readouterr().err
    # nothing ran, nothing written
    assert not (tmp_path / "out").exists()
    with pytest.raises(SystemExit) as exc:
        main([str(tmp_path / "missing.toml"), "--smoke"])
    assert exc.value.code == 2


def test_suite_parallel_artifacts_bit_identical_to_serial(tmp_path, capsys):
    """The acceptance contract: a parallel campaign's artifacts are
    byte-for-byte the files serial `run.py --smoke` writes (synthetic)."""
    from benchmarks.run import main as run_main
    from benchmarks.suite import main as suite_main

    toml = tmp_path / "s.toml"
    toml.write_text('name="tiny"\nparallel=2\ntimer="synthetic"\n'
                    '[[tasks]]\nfamily="bench_peak"\n'
                    '[[tasks]]\nfamily="bench_metg_deps"\nrollouts=2\n')
    suite_dir = tmp_path / "suite"
    suite_main([str(toml), "--smoke", "--artifacts", str(suite_dir)])
    out = capsys.readouterr().out
    assert "suite 'tiny': 3 cell run(s), all ok" in out
    serial_dir = tmp_path / "serial"
    for fam in ("bench_peak", "bench_metg_deps"):
        run_main(["--smoke", "--timer", "synthetic", "--only", fam,
                  "--artifacts", str(serial_dir)])
    capsys.readouterr()
    serial = sorted(os.listdir(serial_dir))
    assert serial == sorted(f for f in os.listdir(suite_dir)
                            if f != "rollouts")
    for f in serial:
        assert ((serial_dir / f).read_bytes()
                == (suite_dir / f).read_bytes()), f


def test_suite_failed_cell_completes_remaining(tmp_path, capsys):
    """A red cell (backends filter matching nothing) exits the suite
    nonzero but the other cells still run and write artifacts."""
    from benchmarks.suite import main as suite_main

    toml = tmp_path / "s.toml"
    toml.write_text('name="redgreen"\ntimer="synthetic"\n'
                    '[[tasks]]\nfamily="bench_metg_scaling"\n'
                    'backends=["xla-scan"]\n'
                    '[[tasks]]\nfamily="bench_peak"\n')
    with pytest.raises(SystemExit) as exc:
        suite_main([str(toml), "--smoke",
                    "--artifacts", str(tmp_path / "out")])
    assert exc.value.code == 1
    out = capsys.readouterr().out
    assert "FAILED bench_metg_scaling" in out
    assert "bench_peak: ok" in out
    assert any(f.startswith("BENCH_peak") for f in
               os.listdir(tmp_path / "out"))


def test_paper_suite_toml_is_valid():
    """The committed campaign document stays loadable and covers every
    registry family exactly once."""
    from benchmarks.run import MODULES

    suite = load_suite(os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks", "suites", "paper.toml"))
    validate_suite(suite, known_families=MODULES)
    assert sorted(c.family for c in suite.cells) == sorted(MODULES)
    assert suite.timer == "synthetic" and suite.parallel > 1
    assert any(c.rollouts > 1 for c in suite.cells)


# ------------------------------------------- registry + tables bug fixes
def test_every_registry_module_runs_with_zero_args():
    """bench_serve_load:38 regression: every MODULES entry's ``run`` must
    be invocable standalone (all parameters defaulted)."""
    from benchmarks.run import MODULES

    for name in MODULES:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        sig = inspect.signature(mod.run)
        missing = [p.name for p in sig.parameters.values()
                   if p.default is inspect.Parameter.empty
                   and p.kind not in (inspect.Parameter.VAR_POSITIONAL,
                                      inspect.Parameter.VAR_KEYWORD)]
        assert not missing, (
            f"{name}.run requires arguments {missing}; standalone "
            f"invocation (no BenchContext) must work for every module")


def test_tables_splice_skipped_on_red_run(tmp_path, capsys):
    """run.py must not regenerate committed tables from a partial
    artifact set: a failed module skips --tables with a stderr note."""
    from benchmarks.run import main

    md = tmp_path / "EXP.md"
    with pytest.raises(SystemExit) as exc:
        main(["--smoke", "--timer", "synthetic",
              "--only", "bench_metg_scaling,bench_peak",
              "--backends", "xla-scan",  # matches nothing -> module fails
              "--artifacts", str(tmp_path), "--tables",
              "--tables-file", str(md)])
    assert exc.value.code == 1
    captured = capsys.readouterr()
    assert "skipping --tables" in captured.err
    assert not md.exists()


def test_load_metg_artifacts_warns_and_counts_skips(tmp_path, capsys):
    """Corrupt/foreign artifacts must not vanish silently from the
    tables: each skip warns naming path + reason, and the count comes
    back to the caller."""
    import append_tables
    from benchmarks.run import main

    main(["--smoke", "--timer", "synthetic", "--only", "bench_peak",
          "--artifacts", str(tmp_path)])
    capsys.readouterr()
    (tmp_path / "BENCH_truncated.json").write_text('{"schema": 1, "ki')
    docs, skipped = append_tables.load_metg_artifacts(str(tmp_path))
    err = capsys.readouterr().err
    assert docs and skipped == 1
    assert "BENCH_truncated.json" in err and "not valid JSON" in err
    # the count propagates through append_metg_tables
    md = tmp_path / "EXP.md"
    path, skipped = append_tables.append_metg_tables(str(tmp_path), str(md))
    assert path == str(md) and skipped == 1
    assert "METG(50%)" in md.read_text()


def test_device_cells_never_share_the_device(tmp_path):
    """A cell that times the device (any timer but the synthetic one)
    runs with no other cell beside it, whatever ``parallel`` says: a chip
    belongs to one process at a time."""
    import json
    import sys

    fake = tmp_path / "benchmarks"
    fake.mkdir()
    (fake / "__init__.py").write_text("")
    (fake / "run.py").write_text(
        "import json, os, sys, time\n"
        "a = sys.argv[1:]\n"
        "out = a[a.index('--artifacts') + 1] + '.' + a[a.index('--only') + 1]\n"
        "t0 = time.time(); time.sleep(0.4)\n"
        "with open(out + '.span', 'w') as f:\n"
        "    json.dump([a[a.index('--timer') + 1], t0, time.time()], f)\n")
    suite = parse_suite(
        'name="s"\nparallel=4\ntimer="synthetic"\n'
        + "".join(f'[[tasks]]\nfamily="{f}"\n' + (
            'timer="wallclock"\n' if i % 2 else "")
            for i, f in enumerate(["a", "b", "c", "d"])))
    res = run_suite(suite, str(tmp_path / "out"), cwd=str(tmp_path),
                    python=sys.executable)
    assert res.ok, res.summary()
    spans = []
    for cr in res.runs:
        with open(f"{cr.out_dir}.{cr.cell.family}.span") as f:
            spans.append(json.load(f))
    assert len(spans) == 4
    for i, (timer, t0, t1) in enumerate(spans):
        if timer == "synthetic":
            continue
        for j, (_, u0, u1) in enumerate(spans):
            assert i == j or u1 <= t0 or t1 <= u0, (spans[i], spans[j])
