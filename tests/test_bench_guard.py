"""Unit tests for the CI bench-regression guard — this is the
"demonstrably fires" requirement: the comparison logic must go red on a
>2.5x slowdown of a same-shape row, stay green otherwise, never compare
rows across shapes, and honor the noisy-runner opt-out."""

import json

import pytest

from benchmarks.check_regression import (
    SKIP_ENV, compare, main, orphaned_rows, shape_key, timed_rows,
)


def _payload(rows, override=None):
    return {"schema": 1, "bench_seeds_override": override, "rows": rows}


def _row(name, us, seeds=None, flows=None, engine=None):
    metrics = {}
    if seeds is not None:
        metrics["seeds"] = seeds
    if flows is not None:
        metrics["flows"] = flows
    row = {"name": name, "us_per_call": us, "derived": "", "metrics": metrics}
    if engine is not None:
        row["engine"] = engine
    return row


def test_fires_on_slowdown_beyond_threshold():
    old = _payload([_row("fig3a_ecmp_fim_pct", 100.0, seeds=1024)])
    new = _payload([_row("fig3a_ecmp_fim_pct", 260.0, seeds=1024)])
    regressions, compared = compare(old, new)
    assert compared == 1
    assert len(regressions) == 1
    assert "fig3a_ecmp_fim_pct" in regressions[0]
    assert "2.60x" in regressions[0]


def test_passes_below_threshold():
    old = _payload([_row("fig3a_ecmp_fim_pct", 100.0, seeds=1024)])
    new = _payload([_row("fig3a_ecmp_fim_pct", 240.0, seeds=1024)])
    regressions, compared = compare(old, new)
    assert compared == 1
    assert regressions == []


def test_absolute_slack_swallows_microsecond_noise():
    """A 3x ratio on a 10us row is timer noise, not a regression."""
    old = _payload([_row("tiny_row", 10.0, seeds=8)])
    new = _payload([_row("tiny_row", 30.0, seeds=8)])
    regressions, _ = compare(old, new)
    assert regressions == []
    # but the same ratio above the slack does fire
    old = _payload([_row("big_row", 100.0, seeds=8)])
    new = _payload([_row("big_row", 300.0, seeds=8)])
    regressions, _ = compare(old, new)
    assert len(regressions) == 1


def test_shape_mismatch_is_never_compared():
    # same row name, but smoke shape vs full shape: not comparable
    old = _payload([_row("mc_paper_ecmp_5tuple", 100.0, seeds=1024)])
    new = _payload([_row("mc_paper_ecmp_5tuple", 9000.0, seeds=8)],
                   override="8")
    regressions, compared = compare(old, new)
    assert compared == 0
    assert regressions == []


def test_same_shape_same_override_compares():
    old = _payload([_row("mc_paper_ecmp_5tuple", 100.0, seeds=8)],
                   override="8")
    new = _payload([_row("mc_paper_ecmp_5tuple", 9000.0, seeds=8)],
                   override="8")
    regressions, compared = compare(old, new)
    assert compared == 1
    assert len(regressions) == 1


def test_derived_only_rows_ignored():
    old = _payload([_row("fig3a_static_fim_pct", 0.0)])
    new = _payload([_row("fig3a_static_fim_pct", 0.0)])
    regressions, compared = compare(old, new)
    assert (regressions, compared) == ([], 0)
    assert timed_rows(new) == {}


def test_new_rows_pass_without_baseline():
    old = _payload([])
    new = _payload([_row("brand_new_bench", 5000.0, seeds=1024)])
    regressions, compared = compare(old, new)
    assert (regressions, compared) == ([], 0)


def test_shape_key_fields():
    payload = _payload([], override="8")
    row = _row("x", 1.0, seeds=8, flows=256)
    assert shape_key(payload, row) == ("x", "8", 8, 256, None)
    row = _row("x", 1.0, seeds=8, flows=256, engine="jax")
    assert shape_key(payload, row) == ("x", "8", 8, 256, "jax")


def test_engine_mismatch_is_orphaned_not_compared():
    """A backend-only difference (same name, same shape) must never
    compare — a numpy->jax swap would otherwise read as a perf
    regression — and the stranded baseline row must surface as
    ORPHANED rather than silently guarding nothing."""
    old = _payload([_row("engine_fill", 100.0, seeds=1024, engine="numpy")])
    new = _payload([_row("engine_fill", 900.0, seeds=1024, engine="jax")])
    regressions, compared = compare(old, new)
    assert (regressions, compared) == ([], 0)
    orphans = orphaned_rows(old, new)
    assert len(orphans) == 1
    assert orphans[0][0] == "engine_fill"
    assert orphans[0][-1] == "numpy"


def test_same_engine_same_shape_compares():
    old = _payload([_row("engine_fill", 100.0, seeds=1024, engine="jax")])
    new = _payload([_row("engine_fill", 900.0, seeds=1024, engine="jax")])
    regressions, compared = compare(old, new)
    assert compared == 1
    assert len(regressions) == 1
    assert "engine=jax" in regressions[0]


def test_main_red_and_green(tmp_path, monkeypatch):
    monkeypatch.delenv(SKIP_ENV, raising=False)
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_payload([_row("b", 100.0, seeds=8)])))
    new.write_text(json.dumps(_payload([_row("b", 1000.0, seeds=8)])))
    assert main(["--old", str(old), "--new", str(new)]) == 1
    new.write_text(json.dumps(_payload([_row("b", 110.0, seeds=8)])))
    assert main(["--old", str(old), "--new", str(new)]) == 0


def test_main_fails_on_zero_comparable_timed_rows(tmp_path, monkeypatch):
    """A stale baseline (renamed rows / drifted shapes) must not let the
    guard pass green forever."""
    monkeypatch.delenv(SKIP_ENV, raising=False)
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_payload([_row("renamed_away", 100.0, seeds=8)])))
    new.write_text(json.dumps(_payload([_row("brand_new", 100.0, seeds=8)])))
    assert main(["--old", str(old), "--new", str(new)]) == 1
    # but an empty baseline (nothing guarded yet) stays green
    old.write_text(json.dumps(_payload([_row("derived_only", 0.0)])))
    assert main(["--old", str(old), "--new", str(new)]) == 0


def test_opt_out_env_var(tmp_path, monkeypatch):
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_payload([_row("b", 100.0, seeds=8)])))
    new.write_text(json.dumps(_payload([_row("b", 99999.0, seeds=8)])))
    monkeypatch.setenv(SKIP_ENV, "1")
    assert main(["--old", str(old), "--new", str(new)]) == 0


def test_custom_threshold():
    old = _payload([_row("b", 100.0, seeds=8)])
    new = _payload([_row("b", 180.0, seeds=8)])
    assert compare(old, new, threshold=1.5)[0]
    assert not compare(old, new, threshold=2.0)[0]


@pytest.mark.parametrize("ratio,fires", [(2.49, False), (2.51, True)])
def test_threshold_boundary(ratio, fires):
    old = _payload([_row("b", 1000.0, seeds=8)])
    new = _payload([_row("b", 1000.0 * ratio, seeds=8)])
    regressions, _ = compare(old, new)
    assert bool(regressions) == fires


def test_orphaned_rows_listed():
    """A baseline row whose bench was renamed or reshaped guards nothing
    — it must be surfaced, not silently skipped."""
    old = _payload([_row("kept", 100.0, seeds=8),
                    _row("renamed_away", 100.0, seeds=8),
                    _row("reshaped", 100.0, seeds=1024)])
    new = _payload([_row("kept", 110.0, seeds=8),
                    _row("reshaped", 100.0, seeds=8),
                    _row("brand_new", 50.0, seeds=8)])
    orphans = orphaned_rows(old, new)
    assert [key[0] for key in orphans] == ["renamed_away", "reshaped"]
    # derived-only baseline rows are not orphans (they never guarded)
    old_derived = _payload([_row("derived_only", 0.0)])
    assert orphaned_rows(old_derived, new) == []


def test_main_prints_orphans_without_failing(tmp_path, monkeypatch, capsys):
    monkeypatch.delenv(SKIP_ENV, raising=False)
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_payload([_row("kept", 100.0, seeds=8),
                                        _row("gone", 100.0, seeds=8)])))
    new.write_text(json.dumps(_payload([_row("kept", 110.0, seeds=8)])))
    assert main(["--old", str(old), "--new", str(new)]) == 0
    out = capsys.readouterr().out
    assert "ORPHANED gone" in out
    assert "refresh the baseline" in out


def test_results_path_anchored_to_repo_root():
    """benchmarks/run.py must write the perf history next to the repo
    root regardless of the CWD it is invoked from — a relative path
    silently desyncs the regression guard."""
    import os

    import benchmarks.run as run

    assert os.path.isabs(run.RESULTS_PATH)
    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(run.__file__)))
    assert run.RESULTS_PATH == os.path.join(repo_root, "BENCH_results.json")


def test_subset_run_merges_into_existing_payload(tmp_path, monkeypatch):
    """A subset invocation must replace only its own benches' rows and
    carry every other bench's rows over — not wipe the history."""
    import json as _json
    import sys

    import benchmarks.common as common
    import benchmarks.run as run

    results_path = tmp_path / "BENCH_results.json"
    monkeypatch.setattr(run, "RESULTS_PATH", str(results_path))
    monkeypatch.setattr(run, "configure_compile_cache", lambda: None)
    monkeypatch.setattr(
        run, "BENCHES",
        {"alpha": lambda: common.emit("alpha_row", 1.0, "v=1"),
         "beta": lambda: common.emit("beta_row", 2.0, "v=2")})

    def run_main(argv):
        monkeypatch.setattr(common, "RESULTS", [])
        monkeypatch.setattr(run, "RESULTS", common.RESULTS)
        monkeypatch.setattr(sys, "argv", ["run"] + argv)
        run.main()
        return _json.loads(results_path.read_text())

    full = run_main([])
    assert {r["name"]: r["bench"] for r in full["rows"]} == {
        "alpha_row": "alpha", "beta_row": "beta"}
    subset = run_main(["beta"])
    assert {r["name"]: r["bench"] for r in subset["rows"]} == {
        "alpha_row": "alpha", "beta_row": "beta"}   # alpha carried over
    assert subset["benches"] == ["beta"]
    # a re-run of a bench replaces, not duplicates, its rows
    assert sum(r["name"] == "beta_row" for r in subset["rows"]) == 1


def test_shape_key_prefers_row_level_override():
    """Rows carried over from an earlier run keep the shape override
    they were measured under, not the merging run's — a full-shape row
    inside a smoke payload must never match a smoke baseline."""
    payload = _payload([], override="8")
    carried = _row("x", 1.0, seeds=8, flows=256)
    carried["bench_seeds_override"] = None      # measured at full shape
    assert shape_key(payload, carried) == ("x", None, 8, 256, None)
    fresh = _row("x", 1.0, seeds=8, flows=256)  # pre-stamp fallback
    assert shape_key(payload, fresh) == ("x", "8", 8, 256, None)


def test_subset_run_carries_prior_errors(tmp_path, monkeypatch):
    """Partial rows of a previously failed bench must keep their error
    record when another bench's subset run rewrites the payload."""
    import json as _json
    import sys

    import benchmarks.common as common
    import benchmarks.run as run

    results_path = tmp_path / "BENCH_results.json"
    monkeypatch.setattr(run, "RESULTS_PATH", str(results_path))
    monkeypatch.setattr(run, "configure_compile_cache", lambda: None)

    def boom():
        common.emit("beta_partial", 1.0, "v=1")
        raise RuntimeError("bench died midway")

    monkeypatch.setattr(
        run, "BENCHES",
        {"alpha": lambda: common.emit("alpha_row", 1.0, "v=1"),
         "beta": boom})

    def run_main(argv):
        monkeypatch.setattr(common, "RESULTS", [])
        monkeypatch.setattr(run, "RESULTS", common.RESULTS)
        monkeypatch.setattr(sys, "argv", ["run"] + argv)
        try:
            run.main()
        except SystemExit:
            pass
        return _json.loads(results_path.read_text())

    failed = run_main([])                        # beta fails, alpha lands
    assert "beta" in failed["errors"]
    clean = run_main(["alpha"])                  # re-run only alpha
    assert "beta" in clean["errors"]             # partial rows still marked
    assert {r["name"] for r in clean["rows"]} == {"alpha_row", "beta_partial"}
    fixed = run_main(["beta"])                   # but beta itself... still red
    assert "beta" in fixed["errors"]


def test_stale_bench_rows_not_carried(tmp_path, monkeypatch):
    """Rows (and errors) of a bench that no longer exists in BENCHES
    must not be carried forward — frozen timings of a renamed bench
    would satisfy the regression guard forever."""
    import json as _json
    import sys

    import benchmarks.common as common
    import benchmarks.run as run

    results_path = tmp_path / "BENCH_results.json"
    results_path.write_text(_json.dumps({
        "schema": 1, "bench_seeds_override": None,
        "rows": [{"name": "old_row", "us_per_call": 5.0, "derived": "",
                  "metrics": {}, "bench": "renamed-away"}],
        "errors": {"renamed-away": "RuntimeError: gone"},
    }))
    monkeypatch.setattr(run, "RESULTS_PATH", str(results_path))
    monkeypatch.setattr(run, "configure_compile_cache", lambda: None)
    monkeypatch.setattr(
        run, "BENCHES", {"alpha": lambda: common.emit("alpha_row", 1.0, "v=1")})
    monkeypatch.setattr(common, "RESULTS", [])
    monkeypatch.setattr(run, "RESULTS", common.RESULTS)
    monkeypatch.setattr(sys, "argv", ["run", "alpha"])
    run.main()
    payload = _json.loads(results_path.read_text())
    assert {r["name"] for r in payload["rows"]} == {"alpha_row"}
    assert "errors" not in payload
