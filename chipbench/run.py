"""FlowTracer on one TPU chip: run one benchmark cell, print one JSON line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root
of the checkout.  Its configuration's frozen inputs
(``configs/<config>/``) and its traffic mix (``traffic/<mix>.json``) are
found by name.  One process, no children: the chip belongs to it.

Set-up (process start to the first timed call, which is ``setup_s``)
imports JAX, points the persistent compilation cache at
``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``, checks the
chip, rebuilds the inputs and makes one warm-up call at the cell's own
shapes.  Then, with ``--trace 0``, one caller calls the cell's front end
back to back until ``--seconds`` have passed, and the window ends when
the call running at the deadline ends.  Every run draws its calls' hash
seeds from the same sequence of seed sets, which ``--seed`` orders
(``call_seeds``), so every run does the same work.  The rate is every
cell (flows x seeds) completed over that whole window; the seconds of
each call are printed on an earlier line.  With ``--trace 1`` a few
calls run under the JAX profiler instead, each inside a host annotation
``call:<front end>``; the trace is reduced (``trace.py``) and each
per-layer metric of the cell is read from it by its reader in
``metrics/<name>.py``.

After the window, a sample of the answers drawn from the seed is
compared with ``reference.py``; each number compared is printed beside
its limit (from the traffic file) as the last lines on standard error,
and under ``check``, last, in the result line.  ``correct`` is true when
every number is within its limit and no call failed.

Exits 2, printing no result, without a TPU, with fewer chips than the
cell asks for, with a device kind missing from ``peaks.json``, or where
the program cannot be imported.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
WARMUP_CALL = 2**32           # call index of the set-up call's seeds


class NoChip(RuntimeError):
    """The run cannot measure: no result is printed."""


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


class CompileLog:
    """Backend compiles (and persistent-cache loads, which JAX reports
    through the same event) seen since the last ``take``."""

    def __init__(self, jax):
        self.count, self.seconds, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def _event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def take(self) -> dict:
        out = {"compiles": self.count, "compile_s": self.seconds,
               "cache_hits": self.hits}
        self.count, self.seconds, self.hits = 0, 0.0, 0
        return out


class Cell:
    """One ``workloads`` entry with everything found by its names."""

    def __init__(self, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config_dir = HERE / "configs" / self.entry["config"]
        self.traffic = load_json(HERE / "traffic"
                                 / f"{self.entry['traffic']}.json")
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def seeds_for(seed: int, call: int, n: int):
    """``n`` hash seeds drawn from ``(seed, call)``."""
    import numpy as np
    ss = np.random.SeedSequence([seed % 2**63, call])
    return np.random.default_rng(ss).integers(
        0, 2**62, n, dtype=np.int64).astype(np.uint64)


def call_seeds(traffic: dict, seed: int, call: int):
    """The hash seeds of one call of the window.

    How much work a call is depends on its hash seeds (the fill iterates
    until the slowest seed's flows are all frozen), so every run draws
    its calls from one sequence of seed sets, fixed by the traffic's
    ``pool_seed``: every run does the same work.  The run seed orders
    the calls within blocks of ``order_block``; no two calls of a run
    share a seed set."""
    import numpy as np
    block = int(traffic["order_block"])
    b, i = divmod(call, block)
    order = np.random.default_rng(
        np.random.SeedSequence([seed % 2**63, 2, b])).permutation(block)
    return seeds_for(int(traffic["pool_seed"]), b * block + int(order[i]),
                     int(traffic["seeds_per_call"]))


def configure_jax():
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    # JAX skips caching programs that compiled in under a second; the
    # small stages would then compile again in every run's set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax, cache


def check_device(jax, chips: int) -> tuple[object, dict]:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devs)}")
    peaks = load_json(HERE / "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    return devs[0], peaks[kind]


def timed_window(fam, seed: int, seconds: float):
    """Closed loop, one caller.  Returns (answers per completed call,
    their seeds, attempted, failed, window seconds, seconds per call)."""
    answers, seeds_done, attempted, failed, call_s = [], [], 0, 0, []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while (t := time.perf_counter()) < deadline:
        seeds = call_seeds(fam.traffic, seed, attempted)
        attempted += 1
        try:
            answers.append(fam.call(seeds))
            seeds_done.append(seeds)
        except Exception:
            traceback.print_exc()
            failed += 1
        call_s.append(time.perf_counter() - t)
    return (answers, seeds_done, attempted, failed,
            time.perf_counter() - t0, call_s)


def traced_calls(jax, fam, seed: int, calls: int, trace_dir: str):
    answers, seeds_done, failed = [], [], 0
    front = fam.traffic["front_end"]
    # the Python tracer would slow the host stages it records and so
    # inflate the idle share; JAX's own host events stay on
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for i in range(calls):
            seeds = call_seeds(fam.traffic, seed, i)
            try:
                with jax.profiler.TraceAnnotation(f"call:{front}"):
                    answers.append(fam.call(seeds))
                seeds_done.append(seeds)
            except Exception:
                traceback.print_exc()
                failed += 1
    finally:
        jax.profiler.stop_trace()
    return answers, seeds_done, calls, failed


def check(fam, answers, seeds_done, seed: int) -> dict:
    """Each number compared over a sample of the answers, drawn from the
    seed: ``traffic["check"]["seeds"]`` seed columns over all calls."""
    import numpy as np
    if not answers:
        return {k: float("inf") for k in fam.traffic["check"]["limits"]}
    n = int(fam.traffic["check"]["seeds"])
    rng = np.random.default_rng(np.random.SeedSequence([seed % 2**63, 1]))
    S = fam.seeds_per_call
    pick = rng.choice(len(answers) * S, size=min(n, len(answers) * S),
                      replace=False)
    numbers: dict[str, float] = {}
    for c in sorted(set(pick // S)):
        idx = np.sort(pick[pick // S == c] % S)
        got = fam.compare(fam.columns(answers[c], idx), seeds_done[c][idx])
        for k, v in got.items():
            numbers[k] = max(numbers.get(k, 0.0), v)
    return numbers


def read_per_layer(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        path = HERE / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{len(out)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(args) -> tuple[dict, list[str]]:
    """One run of one cell.  Returns (result line, stderr lines)."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = Cell(bench, args.workload)
    try:
        jax, cache = configure_jax()
        sys.path.insert(0, str(ROOT / "src"))
        from chipbench import frontends
        import repro.core  # noqa: F401  (the system under test)
    except ImportError as e:
        raise NoChip(f"cannot import the program: {e}") from None
    dev, peaks = check_device(jax, cell.chips)
    log = CompileLog(jax)
    notes = [f"compile cache: {cache}"]

    fam = frontends.family(cell.config_dir, cell.traffic).build()
    fam.call(seeds_for(args.seed, WARMUP_CALL, fam.seeds_per_call))
    setup_s = time.perf_counter() - T_START
    setup = log.take()
    notes.append(f"setup: setup_s={setup_s} compiles={setup['compiles']} "
                 f"compile_s={setup['compile_s']} "
                 f"cache_hits={setup['cache_hits']}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    breakdown = None
    if args.trace:
        from chipbench import trace as tr
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            answers, seeds_done, attempted, failed = traced_calls(
                jax, fam, args.seed, int(cell.traffic["trace_calls"]), tdir)
            window = log.take()
            red = tr.reduce(tr.find_xplane(tdir),
                            f"call:{cell.traffic['front_end']}")
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", 0)
        ctx = tr.Context(
            reduced=red, calls=len(answers), peaks=peaks, peak_bytes=peak,
            kernels=fam.kernels(seeds_done[0]) if seeds_done else [])
        metrics = read_per_layer(cell, ctx)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = red.breakdown()
    else:
        answers, seeds_done, attempted, failed, window_s, call_s = (
            timed_window(fam, args.seed, args.seconds))
        window = log.take()
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", 0)
        rate = len(answers) * fam.cells_per_call / window_s
        metrics = {cell.traffic["metric"]: {
            "value": rate, "unit": "cells/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        notes.append(f"window: window_s={window_s} calls={len(answers)} "
                     f"cells_per_call={fam.cells_per_call}")
        notes.append(f"window: call_s={[round(s, 6) for s in call_s]}")
    notes.append(f"window: compiles={window['compiles']} "
                 f"compile_s={window['compile_s']} "
                 f"cache_hits={window['cache_hits']}")
    device["memory_peak_bytes"] = int(peak)

    # the program's state is host-side by now; the reference runs last
    numbers = check(fam, answers, seeds_done, args.seed)
    limits = cell.traffic["check"]["limits"]
    correct = (failed == 0 and bool(answers)
               and all(numbers.get(k, float("inf")) <= lim
                       for k, lim in limits.items()))
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # a number that could not be taken (no answer, a shape or non-finite
    # mismatch) is printed as null: JSON has no infinity
    result["check"] = {
        k: {"value": v if math.isfinite(v) else None, "limit": lim}
        for k, lim in limits.items()
        for v in [numbers.get(k, float("inf"))]}
    notes += [f"check: {k} {numbers.get(k, float('inf'))!r} limit {lim!r}"
              for k, lim in limits.items()]
    return result, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, notes = run(args)
    except (NoChip, FileNotFoundError, KeyError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for line in notes:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
