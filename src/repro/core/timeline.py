"""Time-expanded simulation: phased collectives over one compiled fabric.

The Monte-Carlo front ends (``monte_carlo_fim`` /
``monte_carlo_throughput``) flatten a workload into ONE steady-state
flow set — fine for the paper's saturating bipartite sweep, wrong for
the phased LLM mixes of ``core/llm_workload.py``: a training step runs
its collectives in *phases* (forward all-gather, MoE all-to-all,
backward reduce-scatter, gradient all-reduce, barrier), so merging them
into a single snapshot both **overstates contention** between
collectives that never share the wire and **hides phase-local
hotspots** that the other phases' flows average away.  Same class of
silent modeling bug the byte-blind FIM (PR 4) and free spraying (PR 5)
were: the simulation answers a question the workload never asks.

This module adds the time axis:

* a schedule is a list of ``TimelineStep``s, each naming the collective
  *channels* (``CollectiveOp.channel_id``) active during that step and a
  relative ``duration``;
* ``simulate_timeline`` partitions one flow list by channel, routes each
  step's active flow set independently over ONE shared
  ``compile_fabric`` pass, and scores each step with the *same* engines
  the merged path uses — ``simulate_paths`` + ``fim_from_counts`` +
  ``throughput_from_result`` — so a one-step schedule containing every
  channel reproduces the merged snapshot **bit-identically** (the
  differential anchor in tests/test_timeline.py);
* ``TimelineResult`` carries the per-step series and the time-weighted
  totals.

**Two timing models** (``SimSpec.timing``):

``timing="static"`` (default) weights steps by their exogenous
``TimelineStep.duration`` constants.  Step durations are relative
durations, not byte shares: with byte-proportional weights the
time-weighted FIM can *never* exceed the merged FIM (the merged load
vector is the byte-weighted mean of the step load vectors, and MAPE is
convex — triangle inequality), which would hide exactly the bug this
module exposes.  Equal default durations model a synchronous schedule —
every phase holds the fabric for one barrier-to-barrier interval
regardless of how many bytes it moves.

``timing="event"`` *derives* each step's duration from the routing
under test: every flow carries its byte volume (``Flow.bytes``, the
emitters attach it per collective), the routed max-min goodput drains
those bytes, flows **depart** as they finish — each departure re-fills
the survivors' rates over the already-computed path tensors
(``vector_throughput.departure_fill``; no re-walk) — and the step ends
when its slowest flow completes.  A routing strategy that collides
badly now looks worse in *time*, not just in FIM: the collision-halved
elephant is the slowest flow, and its lengthened step is exactly the
operator-visible symptom (LLMPrism reconstructs timelines from it;
STrack evaluates load balancing by flow completion time).
``TimelineResult`` then also carries absolute per-step start/end times,
per-flow completion times, and the per-seed **job completion time** —
and the per-step FIM/rate/goodput snapshots are computed exactly as in
static mode, so a one-step schedule stays bit-identical across timings.
Under event timing an ``AdaptiveSpraying`` strategy's round budget is
expressed in RTTs of the derived duration
(``reordering.rtt_round_budget``): the step is first routed with the
static round-1 allocation to derive its length, then re-routed with the
rounds that length affords — so re-spray exposure is charged per unit
time, and a sub-RTT barrier cannot adapt at all.

Schedule emitters for the committed LLM scenarios live in
``core/llm_workload.py`` (``llm_collective_phases`` et al.) with two
modes: ``"sequential"`` (every phase alone, the synchronous-training
default) and ``"dp-overlap"`` (gradient all-reduce overlapped into the
backward phase, the standard DP-overlap optimization).  Channel ids are
registered by name (``register_channel``) so schedule-validation errors
name the ``CH_*`` vocabulary instead of bare ints.
"""

from __future__ import annotations

import dataclasses
import re
from collections.abc import Sequence

import numpy as np

from .compile_fabric import CompiledFabric, compile_fabric
from .fabric import Fabric
from .flows import Flow, WorkloadDescription
from .spans import span
from .strategies import AdaptiveSpraying
from .vector_sim import (
    MonteCarloFim, SimSpec, TIMING_EVENT, TIMING_STATIC, fim_from_counts,
    resolve_flows, resolve_spec, segment_reduce, simulate_paths,
)
from .vector_throughput import (
    MonteCarloThroughput, departure_fill, max_min_rates,
    throughput_from_result,
)

_CHANNEL_RE = re.compile(r"#ch(\d+)$")

#: bytes -> gigabits (the unit ``departure_fill`` drains at Gb/s rates)
_GBITS_PER_BYTE = 8e-9


# ---------------------------------------------------------------------------
# channel registry: ids -> CH_* names, for readable validation errors
# ---------------------------------------------------------------------------

_CHANNEL_NAMES: dict[int, str] = {}


def register_channel(channel_id: int, name: str, *,
                     replace: bool = False) -> int:
    """Name a collective channel id so schedule-validation errors read
    ``4 (CH_MOE_A2A)`` instead of a bare int.

    A duplicate id with a *different* name raises unless
    ``replace=True`` — the same contract as ``register_transport`` /
    ``register_strategy``: silently renaming a channel would relabel
    every schedule that references it.  Re-registering the same
    (id, name) pair is a no-op, so emitter modules can register at
    import time safely.  Returns the id, so emitters can write
    ``CH_FOO = register_channel(7, "CH_FOO")``."""
    cid = int(channel_id)
    if not replace and cid in _CHANNEL_NAMES and _CHANNEL_NAMES[cid] != name:
        raise ValueError(
            f"channel {cid} is already registered as "
            f"{_CHANNEL_NAMES[cid]!r} (known: {known_channels()}); "
            f"pass replace=True to rename it")
    _CHANNEL_NAMES[cid] = name
    return cid


def known_channels() -> list[str]:
    """The registered channel vocabulary, sorted by id, as
    ``"<id> (<name>)"`` strings — what validation errors print."""
    return [f"{cid} ({name})" for cid, name in sorted(_CHANNEL_NAMES.items())]


def channel_name(channel_id: int) -> str:
    """``"<id> (<name>)"`` when registered, the bare id otherwise."""
    name = _CHANNEL_NAMES.get(channel_id)
    return f"{channel_id} ({name})" if name is not None else str(channel_id)


# ---------------------------------------------------------------------------
# schedule vocabulary
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, slots=True)
class TimelineStep:
    """One schedule step: the channels on the wire and how long they hold it.

    ``channels`` are ``CollectiveOp.channel_id`` values (the flow labels
    carry them as the ``#ch<N>`` suffix ``collectives_to_flows`` emits);
    a channel may appear in several steps (an overlapped collective
    spans phases).  ``duration`` is the step's relative duration under
    ``timing="static"`` — see the module docstring for why it is not a
    byte share — and is ignored under ``timing="event"``, where the
    duration is derived from the routed goodput."""

    name: str
    channels: tuple[int, ...]
    duration: float = 1.0

    def __post_init__(self):
        object.__setattr__(self, "channels", tuple(self.channels))
        object.__setattr__(self, "duration", float(self.duration))
        if not self.channels:
            raise ValueError(f"step {self.name!r} has no channels")
        if not self.duration > 0:
            raise ValueError(
                f"step {self.name!r} duration must be > 0, "
                f"got {self.duration}")


def merged_step(schedule: Sequence[TimelineStep],
                name: str = "merged") -> TimelineStep:
    """The degenerate one-step schedule: every channel of ``schedule``
    active at once — the merged-snapshot view the time axis replaces,
    kept as the differential anchor."""
    seen: dict[int, None] = {}
    for step in schedule:
        for ch in step.channels:
            seen.setdefault(ch, None)
    return TimelineStep(name=name, channels=tuple(seen))


def flow_channel(flow: Flow) -> int | None:
    """The collective channel id a flow belongs to, parsed from the
    ``#ch<N>`` label suffix ``collectives_to_flows`` writes.  ``None``
    for unlabeled flows (synthetic bipartite workloads)."""
    m = _CHANNEL_RE.search(flow.label)
    return int(m.group(1)) if m else None


def partition_flows(
    flows: Sequence[Flow], schedule: Sequence[TimelineStep]
) -> list[list[Flow]]:
    """Each step's active flow sublist, in original flow order (order
    preservation is what makes the one-step schedule bit-identical to
    the merged run).

    Validation is strict in both directions — silently dropping traffic
    *or* silently simulating an idle step is exactly the class of bug
    this module exists to remove:

    * flows whose channel appears in no step raise (unscheduled
      traffic);
    * flows without a ``#ch<N>`` label raise (unattributable traffic);
    * a step referencing a channel that no flow carries — unknown id or
      legitimately empty collective — raises, naming the known channels
      (``register_channel`` vocabulary), so emitters must filter absent
      phases explicitly (``llm_schedule`` does).
    """
    if not flows:
        raise ValueError(
            "no flows to partition: the flow list is empty, so every "
            "schedule step would resolve to an empty flow set")
    chans = [flow_channel(f) for f in flows]
    unlabeled = sum(c is None for c in chans)
    if unlabeled:
        raise ValueError(
            f"{unlabeled} flows carry no '#ch<N>' label — "
            f"time-expanded simulation needs collective-derived flows "
            f"(see core/llm_workload.py)")
    present = {c for c in chans if c is not None}
    for step in schedule:
        missing = sorted(set(step.channels) - present)
        if missing:
            raise ValueError(
                f"step {step.name!r} references channel(s) "
                f"{[channel_name(c) for c in missing]} that no flow "
                f"carries; known channels here: "
                f"{[channel_name(c) for c in sorted(present)]} "
                f"(registered vocabulary: {known_channels()})")
    covered = {ch for step in schedule for ch in step.channels}
    stray = sorted({c for c in present if c not in covered})
    if stray:
        raise ValueError(
            f"flows on channels {stray} appear in no schedule step "
            f"(steps cover {sorted(covered)}); every collective must be "
            f"scheduled somewhere")
    return [[f for f, c in zip(flows, chans) if c in step.channels]
            for step in schedule]


def step_byte_totals(flows: Sequence[Flow],
                     schedule: Sequence[TimelineStep]) -> np.ndarray:
    """(K,) total wire bytes active during each step — the byte totals
    the ``llm_workload`` emitters attach to a schedule through the
    flows' ``#ch`` labels, and what ``timing="event"`` drains.  Shares
    ``partition_flows``'s strict validation; an overlapped flow (its
    channel in several steps) counts toward every step it is active in."""
    parts = partition_flows(flows, schedule)
    return np.array([float(sum(f.bytes for f in sub)) for sub in parts],
                    np.float64)


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StepResult:
    """One step's full scoring: the routed flow set, FIM distribution,
    and throughput/goodput distribution — exactly what the merged
    pipeline would report had this step been the whole workload.

    Under ``timing="event"`` two more series appear: ``completion`` is
    the per-(flow, seed) completion time in seconds *relative to the
    step's start* (the departure-ordered drain of the flow's bytes) and
    ``duration`` the per-seed step duration — the completion of the
    slowest flow.  ``None`` under static timing."""

    step: TimelineStep
    flows: list[Flow]
    fim: MonteCarloFim
    throughput: MonteCarloThroughput
    completion: np.ndarray | None = None   # (N, S) seconds from step start
    duration: np.ndarray | None = None     # (S,) seconds

    @property
    def mean_goodput(self) -> np.ndarray:
        """(S,) mean per-flow goodput under each seed."""
        return self.throughput.goodput.mean(axis=0)

    @property
    def mean_rate(self) -> np.ndarray:
        """(S,) mean per-flow max-min rate under each seed."""
        return self.throughput.rates.mean(axis=0)


@dataclasses.dataclass
class TimelineResult:
    """Per-step series + time-weighted totals of a scheduled simulation.

    The totals weight each step by its normalized duration: ``fim`` is
    the duration-weighted mean of the per-step aggregate FIM — "the
    imbalance a uniformly-time-sampling observer sees" — and ``goodput``
    / ``rates`` the duration-weighted mean of per-step mean flow
    goodput/rate.  For a one-step schedule every series is the step's
    own, bit-identically.

    Under ``timing="static"`` the weights are the exogenous
    ``TimelineStep.duration`` constants (normalized, identical across
    seeds).  Under ``timing="event"`` each *seed* has its own derived
    step durations, so the totals are weighted per seed and the result
    additionally carries the absolute time axis: ``step_durations`` /
    ``step_starts`` / ``step_ends`` are ``(K, S)`` seconds (steps run
    back to back in schedule order — the synchronous-training contract),
    and ``job_completion`` is the per-seed end of the last step: the
    training-step wall-clock a collision-lengthened elephant directly
    inflates.  ``weights`` then reports the seed-mean duration shares
    (display/compat; the totals use the exact per-seed shares)."""

    seeds: np.ndarray                   # (S,)
    steps: list[StepResult]
    weights: np.ndarray                 # (K,) normalized step durations
    fim: np.ndarray                     # (S,) time-weighted aggregate FIM
    goodput: np.ndarray                 # (S,) time-weighted mean goodput
    rates: np.ndarray                   # (S,) time-weighted mean rate
    timing: str = TIMING_STATIC
    step_durations: np.ndarray | None = None   # (K, S) seconds (event)
    step_starts: np.ndarray | None = None      # (K, S) absolute seconds
    step_ends: np.ndarray | None = None        # (K, S) absolute seconds
    job_completion: np.ndarray | None = None   # (S,) seconds (event)

    @property
    def num_steps(self) -> int:
        return len(self.steps)

    def step_fim(self) -> np.ndarray:
        """(K, S) per-step aggregate FIM series."""
        return np.stack([s.fim.aggregate for s in self.steps])

    def flow_completion(self, step_index: int) -> np.ndarray:
        """(N, S) *absolute* completion times (seconds from job start)
        of step ``step_index``'s flows — the step's relative departure
        times shifted by its start.  Event timing only."""
        if self.timing != TIMING_EVENT:
            raise ValueError(
                "flow_completion is only defined under timing='event' "
                f"(this result is timing={self.timing!r})")
        return (self.step_starts[step_index]
                + self.steps[step_index].completion)

    def summary(self) -> dict[str, dict[str, float]]:
        rows: dict[str, np.ndarray] = {
            "fim": self.fim,
            "goodput": self.goodput,
            "rate": self.rates,
        }
        if self.job_completion is not None:
            rows["job_completion_s"] = self.job_completion
        for sr in self.steps:
            rows[f"fim[{sr.step.name}]"] = sr.fim.aggregate
            rows[f"goodput[{sr.step.name}]"] = sr.mean_goodput
            if sr.duration is not None:
                rows[f"duration_s[{sr.step.name}]"] = sr.duration
        out = {}
        for name, v in rows.items():
            v = np.asarray(v, np.float64).ravel()
            out[name] = {
                "mean": float(v.mean()),
                "std": float(v.std()),
                "min": float(v.min()),
                "p50": float(np.percentile(v, 50)),
                "max": float(v.max()),
            }
        return out


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def _score_step(comp, sub, seeds, s, layers, only_used_leaves):
    """Route + score one step's flow set: the identical pipeline the
    merged front ends run, with the flowlet fill shared between the
    throughput snapshot and (under event timing) the departure drain."""
    res = simulate_paths(comp, sub, seeds, spec=s)
    with span("timeline.fim"):
        agg, per_layer = fim_from_counts(
            res.link_flow_counts(), comp,
            layers=layers, only_used_leaves=only_used_leaves)
    flowlet_rates = max_min_rates(res, engine=s.engine)
    tp = throughput_from_result(res, transport=s.transport,
                                engine=s.engine,
                                flowlet_rates=flowlet_rates)
    fim = MonteCarloFim(seeds=res.seeds, aggregate=agg, per_layer=per_layer)
    return res, fim, tp, flowlet_rates


def _event_step_times(res, tp, flowlet_rates):
    """((N, S) per-flow completion seconds, (S,) step duration) of one
    routed step under the departure-ordered drain.

    Each tensor column drains its byte share — the parent flow's bytes
    times the flowlet's demand fraction — at goodput = max-min rate x
    transport efficiency.  Efficiency comes from the committed routing's
    exposure (held fixed across departures, see ``departure_fill``); the
    full-set fill is reused as round 1, so event timing adds only the
    departure re-fills on top of the static cost.  Byte volumes are
    floored at one byte: a zero-byte control flow completes in epsilon
    time rather than zero, keeping every step's duration positive (the
    duration-share weighting needs a nonzero total)."""
    fi = np.asarray(res.flow_index)
    bytes_f = np.array([f.bytes for f in res.flows], np.float64)
    gbits_f = np.maximum(bytes_f, 1.0) * _GBITS_PER_BYTE
    col_gbits = gbits_f[fi] * np.asarray(res.demand, np.float64)
    w = res.column_weights()
    dep = departure_fill(
        res.link_ids, res.compiled.link_gbps, col_gbits,
        weights=None if (w == 1.0).all() else w,
        efficiency=np.asarray(tp.efficiency)[fi],
        assume_unique=True, initial_rates=flowlet_rates)
    # a flow completes when its last flowlet does
    completion = np.ascontiguousarray(segment_reduce(
        dep.completion, fi, res.num_flows, np.maximum, 0.0))
    return completion, dep.duration


def simulate_timeline(
    fabric: Fabric | CompiledFabric,
    workload: WorkloadDescription | Sequence[Flow],
    schedule: Sequence[TimelineStep],
    seeds: Sequence[int] | np.ndarray,
    *,
    spec: SimSpec | None = None,
    layers: Sequence[str] | None = None,
    only_used_leaves: bool = False,
) -> TimelineResult:
    """Simulate a phase schedule step by step over one compiled fabric.

    Every step routes ONLY its active flows (the others are off the wire
    — that is the fix), through the identical ``simulate_paths`` →
    ``fim_from_counts`` → ``throughput_from_result`` pipeline the merged
    front ends run, under the same ``spec=`` (``spec.strategy`` accepts
    a registry name string or instance, resolved once up front and
    shared by every step; ``engine="jax"`` routes every step through
    the device engine).  The compiled fabric is shared across steps; a
    ``CompiledFabric`` passes through unchanged, so sweeps over
    schedules or strategies pay compilation once.

    ``timing="static"`` (the default) weights the totals by the
    exogenous ``TimelineStep.duration`` constants.  ``timing="event"`` derives
    each step's duration from the routed goodput instead — flows depart
    as their bytes finish (``departure_fill``), the step ends with its
    slowest flow — and fills in the absolute time axis on the result
    (``step_starts`` / ``step_ends`` / ``job_completion``, per-flow
    ``StepResult.completion``).  The per-step FIM/rate/goodput
    *snapshots* are computed identically under both timings (full
    active-set allocation), so a one-step schedule is bit-identical
    across timings and to the merged front ends.  Under event timing an
    ``AdaptiveSpraying`` step is first routed at its static round-1
    allocation to derive the duration, then re-routed with the round
    budget that duration affords in transport RTTs
    (``rtt_round_budget`` — re-spray exposure priced per unit time).

    Schedules are validated strictly (``partition_flows``): stray flows,
    unlabeled flows, and steps whose channels no flow carries all raise
    — nothing is silently dropped or silently idle.
    """
    s = resolve_spec(spec)
    comp = (fabric if isinstance(fabric, CompiledFabric)
            else compile_fabric(fabric))
    flows = resolve_flows(comp, workload)
    if not schedule:
        raise ValueError("schedule must contain at least one step")
    parts = partition_flows(flows, schedule)
    event = s.timing == TIMING_EVENT
    # AdaptiveSpraying under event timing: probe with the static round-1
    # allocation first, then spend the RTT budget the duration affords
    adaptive = (event and isinstance(s.strategy, AdaptiveSpraying)
                and s.strategy.rounds > 1)
    if adaptive:
        from .reordering import IDEAL, rtt_round_budget
        rtt = (s.transport.rtt_seconds if s.transport is not None
               else IDEAL.rtt_seconds)
    steps: list[StepResult] = []
    durations: list = []
    for step, sub in zip(schedule, parts):
        spec_k = (dataclasses.replace(s, strategy=s.strategy.with_rounds(1))
                  if adaptive else s)
        res, fim_k, tp, fr = _score_step(comp, sub, seeds, spec_k,
                                         layers, only_used_leaves)
        if not event:
            steps.append(StepResult(step=step, flows=sub, fim=fim_k,
                                    throughput=tp))
            durations.append(step.duration)
            continue
        completion, duration = _event_step_times(res, tp, fr)
        if adaptive:
            budget = rtt_round_budget(float(duration.mean()), rtt,
                                      s.strategy.rounds)
            if budget > 1:
                spec_k = dataclasses.replace(
                    s, strategy=s.strategy.with_rounds(budget))
                res, fim_k, tp, fr = _score_step(
                    comp, sub, seeds, spec_k, layers, only_used_leaves)
                completion, duration = _event_step_times(res, tp, fr)
        steps.append(StepResult(step=step, flows=sub, fim=fim_k,
                                throughput=tp, completion=completion,
                                duration=duration))
        durations.append(duration)
    if not event:
        w = np.asarray(durations, np.float64)
        w = w / w.sum()
        if len(steps) == 1:
            # the degenerate anchor: no weighting arithmetic may perturb it
            fim = steps[0].fim.aggregate
            goodput = steps[0].mean_goodput
            rates = steps[0].mean_rate
        else:
            fim = np.einsum("k,ks->s", w, np.stack(
                [s_.fim.aggregate for s_ in steps]))
            goodput = np.einsum("k,ks->s", w, np.stack(
                [s_.mean_goodput for s_ in steps]))
            rates = np.einsum("k,ks->s", w, np.stack(
                [s_.mean_rate for s_ in steps]))
        return TimelineResult(seeds=steps[0].fim.seeds, steps=steps,
                              weights=w, fim=fim, goodput=goodput,
                              rates=rates, timing=s.timing)
    dmat = np.stack(durations)             # (K, S) derived seconds
    ends = np.cumsum(dmat, axis=0)         # steps run back to back
    starts = ends - dmat
    job = ends[-1]
    w = dmat.mean(axis=1)
    w = w / w.sum()
    if len(steps) == 1:
        # the degenerate anchor: no weighting arithmetic may perturb it
        fim = steps[0].fim.aggregate
        goodput = steps[0].mean_goodput
        rates = steps[0].mean_rate
    else:
        wks = dmat / dmat.sum(axis=0)      # per-seed duration shares
        fim = (wks * np.stack(
            [s_.fim.aggregate for s_ in steps])).sum(axis=0)
        goodput = (wks * np.stack(
            [s_.mean_goodput for s_ in steps])).sum(axis=0)
        rates = (wks * np.stack(
            [s_.mean_rate for s_ in steps])).sum(axis=0)
    return TimelineResult(seeds=steps[0].fim.seeds, steps=steps,
                          weights=w, fim=fim, goodput=goodput, rates=rates,
                          timing=s.timing, step_durations=dmat,
                          step_starts=starts, step_ends=ends,
                          job_completion=job)
