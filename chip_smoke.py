"""Smoke run of the jax engine's main path on one TPU chip.

    python chip_smoke.py [--seed N]

Drives ``engine="jax"`` through the front ends a user calls
(``monte_carlo_fim``, ``monte_carlo_throughput``, ``simulate_paths`` /
``throughput_from_result``, ``simulate_timeline``) and the compiled
flowhash Pallas kernel, and checks every result against the numpy engine
on a seed subsample.  Each phase runs three times, each after the
one before: cold (JAX's in-memory caches cleared, so every stage
compiles or loads what an earlier phase put in the persistent cache),
cached (in-memory caches cleared again, every stage loads from the
persistent cache) and warm.  Each phase prints one JSON line with its
shape, resolved hash backend, wall times (taken once the result is on
the host), the per-stage compile seconds of the cold and cached runs,
the largest difference from the reference and the device's
``peak_bytes_in_use`` so far.  A phase also fails when one of the
engine's jitted stages it must reach did not compile in its cold run:
no stage falls back silently.  The last line is
``{"ok": true, "device": {...}}``.

Exits non-zero, printing no result, when JAX finds no TPU, when a phase
raises, or when any result differs from the reference.  One process,
no children: the chip belongs to this process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
FLOWS_PER_PAIR = 256          # 16 directed server pairs -> 4096 flows
SCALE_FLOWS_PER_PAIR = 6250   # -> 100 000 flows
NUM_SEEDS = 1024
# Seeds cut where the fill's run time would not fit the run's time
# limit: on a v5e one fill pass takes ~24 s at 4096 flows x 1024 seeds,
# and each phase runs three times.  Flows are never cut.
SPRAY_SEEDS = 256
SCALE_THROUGHPUT_SEEDS = 128  # one lane-tile pass of 100k flows
SUBSAMPLE = 64                # seeds checked against the numpy engine
SCALE_SUBSAMPLE = 16
PEAK_LIMIT = 8 * 10**9        # half of one v5e's 16 GB of HBM
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Backend compile seconds per jitted function name, from JAX's
    monitoring events; a persistent-cache hit reports its load time."""

    def __init__(self, jax):
        self.times: dict[str, float] = {}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            name = str(kw.get("fun_name", "?"))
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]
            self.times[name] = self.times.get(name, 0.0) + duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> tuple[dict[str, float], int]:
        out = (self.times, self.cache_hits)
        self.times, self.cache_hits = {}, 0
        return out


def paper_flows(flows_per_pair: int):
    from repro.core import (
        bipartite_pairs, nic_ip, server_name, synthesize_flows,
    )
    rack0 = [server_name(i) for i in range(8)]
    rack1 = [server_name(8 + i) for i in range(8)]
    wl = bipartite_pairs(rack0, rack1, flows_per_pair=flows_per_pair)
    return synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)


def max_abs(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        raise ValueError(f"shape {a.shape} != reference {b.shape}")
    if not np.array_equal(np.isinf(a), np.isinf(b)):
        return float("inf")
    fin = np.isfinite(b)
    return float(np.abs(a[fin] - b[fin]).max(initial=0.0))


def max_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape or not (np.isfinite(a).all() and b.all()):
        return float("inf")
    return float((np.abs(a - b) / np.abs(b)).max(initial=0.0))


def fim_diff(jx, ref, n: int) -> float:
    if sorted(jx.per_layer) != sorted(ref.per_layer):
        return float("inf")
    diffs = [max_abs(jx.aggregate[:n], ref.aggregate)]
    diffs += [max_abs(jx.per_layer[k][:n], ref.per_layer[k])
              for k in ref.per_layer]
    return max(diffs)


def build_phases(seed: int, backend: str):
    """(name, shape, hash_backend, stages, run, check, tol) per phase.
    ``stages`` are the jitted stages the cold run must compile; ``run``
    returns host results; ``check`` returns the largest difference from
    the numpy engine (0 for bit-identical checks)."""
    from repro.core import (
        CH_GRAD_AR, CH_MOE_A2A, ELEPHANT_MIN_BYTES, FIELDS_5TUPLE, SimSpec,
        TIMING_EVENT, TimelineStep, build_multipod_fabric,
        build_paper_testbed, compile_fabric, flow_channel,
        monte_carlo_fim, monte_carlo_throughput, multipod_llm_schedule,
        simulate_paths, simulate_timeline,
    )
    from repro.core.ecmp import flow_fields_matrix
    from repro.core.vector_sim import EXACT
    from repro.kernels.flowhash import ops as flowhash
    from repro.kernels.flowhash.ref import bulk_hash_seeded_ref

    rng = np.random.default_rng(seed)
    seeds = rng.integers(0, 2**62, NUM_SEEDS)
    sub = seeds[:SUBSAMPLE]
    comp = compile_fabric(build_paper_testbed())
    flows = paper_flows(FLOWS_PER_PAIR)
    jx = dict(engine="jax")
    ref = dict(hash_backend=backend)
    phases = []

    # -- paper testbed, fused plain-ECMP front ends ----------------------
    def fim_check(out):
        r = monte_carlo_fim(comp, flows, sub, **ref)
        d = fim_diff(out, r, SUBSAMPLE)
        a = simulate_paths(comp, flows, sub, **jx).link_flow_counts()
        b = simulate_paths(comp, flows, sub, **ref).link_flow_counts()
        return d if np.array_equal(a, b) else float("inf")

    phases.append((
        "paper-fim", f"flows={len(flows)} seeds={NUM_SEEDS}", backend,
        {"walk", "counts_fn", "fim_fn"},
        lambda: monte_carlo_fim(comp, flows, seeds, **jx), fim_check, 1e-9))

    def tp_check(out):
        r = monte_carlo_throughput(comp, flows, sub, transport="roce-nack",
                                   **ref)
        return max(max_abs(out.rates[:, :SUBSAMPLE], r.rates),
                   max_abs(out.goodput[:, :SUBSAMPLE], r.goodput))

    phases.append((
        "paper-throughput", f"flows={len(flows)} seeds={NUM_SEEDS}", backend,
        {"walk", "fill"},
        lambda: monte_carlo_throughput(comp, flows, seeds,
                                       transport="roce-nack", **jx),
        tp_check, 1e-6))

    # -- paper testbed, demand-aware spraying (non-fused route) ---------
    big = [dataclasses.replace(
        f, bytes=(4 * ELEPHANT_MIN_BYTES if i % 4 == 0 else 1024 * 1024))
        for i, f in enumerate(flows)]
    spray = dict(strategy="prime-spray-elephant", demand_mode="bytes",
                 transport="roce-nack")

    def spray_check(out):
        r = monte_carlo_throughput(comp, big, sub, **spray, **ref)
        return max(max_abs(out.rates[:, :SUBSAMPLE], r.rates),
                   max_abs(out.goodput[:, :SUBSAMPLE], r.goodput))

    phases.append((
        "paper-spray", f"flows={len(big)} seeds={SPRAY_SEEDS}", backend,
        {"walk", "fill", "exposure_fn"},
        lambda: monte_carlo_throughput(comp, big, seeds[:SPRAY_SEEDS],
                                       **spray, **jx),
        spray_check, 1e-6))

    # -- exact splitmix64 walk: emulated uint64 on the chip --------------
    few = flows[:256]

    def exact_check(out):
        r = simulate_paths(comp, few, sub, hash_backend=EXACT)
        same = out.link_ids.shape == r.link_ids.shape and np.array_equal(
            out.link_ids, r.link_ids)
        return 0.0 if same else float("inf")

    phases.append((
        "exact-walk", f"flows={len(few)} seeds={SUBSAMPLE}", EXACT,
        {"walk"},
        lambda: simulate_paths(comp, few, sub, hash_backend=EXACT, **jx),
        exact_check, 0.0))

    # -- wave placement above its depth cutover: the jax wave walk -------
    wave = dict(strategy="wave-congestion-aware")

    def wave_check(out):
        r = simulate_paths(comp, flows, sub, **wave, **ref)
        same = out.link_ids.shape == r.link_ids.shape and np.array_equal(
            out.link_ids, r.link_ids)
        return 0.0 if same else float("inf")

    phases.append((
        "paper-wave", f"flows={len(flows)} seeds={SUBSAMPLE}", backend,
        {"walk", "wave_walk"},
        lambda: simulate_paths(comp, flows, sub, **wave, **jx),
        wave_check, 0.0))

    # -- multipod LLM schedule, event-timed ------------------------------
    mcomp = compile_fabric(build_multipod_fabric())
    _, mflows, _, _ = multipod_llm_schedule(param_bytes=20_000_000_000)
    msub = [f for f in mflows if flow_channel(f) in (CH_GRAD_AR, CH_MOE_A2A)]
    sched = [TimelineStep("grad-all-reduce", (CH_GRAD_AR,)),
             TimelineStep("moe-all-to-all", (CH_MOE_A2A,))]
    for strategy in ("ecmp", "wave-congestion-aware"):
        spec = SimSpec(demand_mode="bytes", strategy=strategy,
                       timing=TIMING_EVENT, hash_backend=backend)

        def event_check(out, spec=spec):
            r = simulate_timeline(mcomp, msub, sched, sub, spec=spec)
            if max_abs(out.fim, r.fim) > 1e-6:
                return float("inf")
            return max_rel(out.job_completion, r.job_completion)

        phases.append((
            f"multipod-event/{strategy}",
            f"flows={len(msub)} steps={len(sched)} seeds={SUBSAMPLE}",
            backend,
            # below the wave's depth cutover (~0.5 flows per link here)
            # the wave hands the schedule to sequential CongestionAware,
            # so only the fill runs on the device
            {"walk", "fill"} if strategy == "ecmp" else {"fill"},
            lambda spec=spec: simulate_timeline(
                mcomp, msub, sched, sub,
                spec=dataclasses.replace(spec, engine="jax")),
            event_check, 1e-6))

    # -- scale: 100k flows through the fused front ends ------------------
    scale = paper_flows(SCALE_FLOWS_PER_PAIR)
    ssub = seeds[:SCALE_SUBSAMPLE]

    def scale_run():
        return (monte_carlo_fim(comp, scale, seeds, **jx),
                monte_carlo_throughput(
                    comp, scale, seeds[:SCALE_THROUGHPUT_SEEDS], **jx))

    def scale_check(out):
        fim, tp = out
        d = fim_diff(fim, monte_carlo_fim(comp, scale, ssub, **ref),
                     SCALE_SUBSAMPLE)
        r = monte_carlo_throughput(comp, scale, ssub, **ref)
        return max(d, max_abs(tp.rates[:, :SCALE_SUBSAMPLE], r.rates))

    phases.append((
        "scale", f"flows={len(scale)} fim_seeds={NUM_SEEDS} "
        f"throughput_seeds={SCALE_THROUGHPUT_SEEDS}", backend,
        {"walk", "counts_fn", "fim_fn", "fill"}, scale_run, scale_check, 1e-6))

    # -- the compiled flowhash kernel -------------------------------------
    fields = flow_fields_matrix(flows, FIELDS_5TUPLE).astype(np.uint32)
    row_seeds = rng.integers(0, 2**32, len(flows)).astype(np.uint32)

    def kernel_check(out):
        lowered = flowhash._bulk_hash_seeded_impl.lower(
            fields, row_seeds, force_kernel=True, interpret=False,
            block=4096).as_text()
        if "tpu_custom_call" not in lowered:
            return float("inf")
        r = np.asarray(bulk_hash_seeded_ref(fields, row_seeds[:, None]))[:, 0]
        return 0.0 if np.array_equal(out, r) else float("inf")

    phases.append((
        "flowhash-kernel", f"rows={len(flows)} fields={fields.shape[1]}",
        "murmur", {"_bulk_hash_seeded_impl"},
        lambda: np.asarray(flowhash.bulk_hash_seeded(
            fields, row_seeds, force_kernel=True)),
        kernel_check, 0.0))
    return phases


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the hash seeds and kernel inputs")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import configure_compile_cache
    from repro.core import resolve_hash_backend

    cache_dir = configure_compile_cache()
    print(f"chip_smoke: compile cache at {cache_dir}", file=sys.stderr)
    log = CompileLog(jax)
    backend = resolve_hash_backend(None, "jax")
    failed = []
    phases = build_phases(args.seed, backend)
    for name, shape, hb, stages, run, check, tol in phases:
        try:
            jax.clear_caches()
            log.take()
            _, cold = timed(run)
            cold_compile, _ = log.take()
            jax.clear_caches()
            _, cached = timed(run)
            cache_compile, hits = log.take()
            out, warm = timed(run)
            diff = check(out)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            continue
        missing = sorted(stages - set(cold_compile))
        ok = diff <= tol and not missing
        if not ok:
            failed.append(name)
        print(json.dumps({
            "phase": name, "shape": shape, "hash_backend": hb,
            "missing_stages": missing,
            "cold_s": cold, "cached_s": cached, "warm_s": warm,
            "compile_cold_s": cold_compile, "compile_cached_s": cache_compile,
            "cache_hits": hits, "max_diff": diff, "tol": tol, "ok": ok,
            "peak_bytes_in_use": dev.memory_stats().get("peak_bytes_in_use"),
        }), flush=True)
        if name == "scale" and not (
                dev.memory_stats().get("peak_bytes_in_use", 0) < PEAK_LIMIT):
            failed.append("scale-peak-memory")
    if failed:
        print(f"chip_smoke: failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
