"""Benchmark harness: one module per paper figure + beyond-paper extras.
Prints ``name,us_per_call,derived`` CSV rows and writes the same rows to
``BENCH_results.json`` (always at the repo root, wherever invoked from)
so the perf trajectory is machine-trackable across PRs.  Rows carry a
``bench`` tag and a subset invocation replaces only its own benches'
rows, carrying the rest of the existing payload over — so a quick
``fig3a`` check never wipes the other benches' history (rows carried
from a different ``BENCH_SEEDS`` shape surface as ORPHANED in the
regression guard rather than silently matching).

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig3a ...  # subset
    BENCH_SEEDS=8 python -m benchmarks.run fig3a       # tiny smoke shapes
"""

import json
import os
import platform
import sys
import time
import traceback

from repro.compile_cache import configure_compile_cache

from . import (
    bulk_scale, fig3a_routing_comparison, fig3bc_flow_distributions,
    fig4_thread_scaling, fig5_connection_strategies, goodput, hetero_demand,
    jax_engine, monte_carlo_fim, placement_ablation, roofline,
    throughput_sweep, timeline, vxlan_entropy, wave_route,
)
from .common import RESULTS

BENCHES = {
    "fig3a": fig3a_routing_comparison.run,
    "fig3bc": fig3bc_flow_distributions.run,
    "fig4": fig4_thread_scaling.run,
    "fig5": fig5_connection_strategies.run,
    "bulk_scale": bulk_scale.run,
    "goodput": goodput.run,
    "hetero": hetero_demand.run,
    "monte_carlo": monte_carlo_fim.run,
    "throughput": throughput_sweep.run,
    "timeline": timeline.run,
    "jax_engine": jax_engine.run,
    "wave_route": wave_route.run,
    "placement": placement_ablation.run,
    "vxlan": vxlan_entropy.run,
    "roofline": roofline.run,
}

# anchored to the repo root (the parent of this package), NOT the CWD:
# a relative path would scatter perf history wherever the harness happens
# to be invoked from and silently desync the CI regression guard
RESULTS_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_results.json")


def carried_state(path: str, names: list[str]) -> tuple[list[dict],
                                                        dict[str, str]]:
    """(rows, errors) of benches NOT in this run, carried over from the
    existing payload so a subset invocation updates its own rows instead
    of wiping every other bench's trajectory.  Errors travel with their
    rows: a bench that failed partway leaves partial rows, and dropping
    its error record would launder them into a clean-looking payload.
    Rows are attributed via the ``bench`` tag stamped below; untagged
    rows (pre-tag payloads), rows of benches that no longer exist in
    ``BENCHES`` (renamed/deleted — carrying their frozen timings forward
    would let them satisfy the regression guard forever), and unreadable
    files carry nothing."""
    try:
        with open(path) as f:
            prior = json.load(f)
    except (OSError, ValueError):
        return [], {}
    keep = set(BENCHES) - set(names)
    rows = [r for r in prior.get("rows", []) if r.get("bench") in keep]
    errors = {bench: msg for bench, msg in prior.get("errors", {}).items()
              if bench in keep}
    return rows, errors


def main() -> None:
    names = sys.argv[1:] or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown bench(es): {unknown}; have {list(BENCHES)}")
    configure_compile_cache()
    print("name,us_per_call,derived")
    errors: dict[str, str] = {}
    for name in names:
        # a failing bench must not silently truncate the run: the rest of
        # the matrix still executes and lands rows, the failure is recorded
        # in the payload, and the process exits non-zero at the end
        before = len(RESULTS)
        try:
            BENCHES[name]()
        except Exception as exc:
            traceback.print_exc()
            errors[name] = f"{type(exc).__name__}: {exc}"
        # per-row provenance: the owning bench (subset-merge attribution)
        # and the shape override it ran under, so carried-over rows keep
        # their true shape identity in the regression guard
        for row in RESULTS[before:]:
            row["bench"] = name
            row["bench_seeds_override"] = os.environ.get("BENCH_SEEDS")
    prior_rows, prior_errors = carried_state(RESULTS_PATH, names)
    payload = {
        "schema": 1,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "python": platform.python_version(),
        "machine": platform.machine(),
        "benches": names,
        # smoke runs (BENCH_SEEDS=8 in CI) are tagged so trajectory
        # tooling never mistakes tiny-shape numbers for the baseline
        "bench_seeds_override": os.environ.get("BENCH_SEEDS"),
        "rows": prior_rows + RESULTS,
    }
    if errors or prior_errors:
        payload["errors"] = {**prior_errors, **errors}
    with open(RESULTS_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    if errors:
        raise SystemExit(
            f"bench module(s) failed: {sorted(errors)} "
            f"(partial rows written to {RESULTS_PATH})")


if __name__ == "__main__":
    main()
