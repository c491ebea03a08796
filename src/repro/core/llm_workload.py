"""Synthetic LLM-training collective mixes -> byte-weighted DCN flows.

The paper's workload description (Step 1) names server pairs *and* flow
volumes, and real LLM training traffic is heavily non-uniform across the
parallelism axes (LLMPrism): one data-parallel gradient all-reduce moves
gigabytes per ring edge while a barrier moves bytes, with FSDP
all-gather / reduce-scatter and MoE all-to-all in between.  This module
generates that mix *without* needing a compiled HLO dump: it constructs
the same ``CollectiveOp`` records ``hlo_flows.extract_collectives``
would parse — ring all-reduce / all-gather / reduce-scatter over
cross-host rings, expert-parallel all-to-all over EP groups, a tiny
control barrier — and reuses ``collectives_to_flows`` for the
byte-accurate decomposition into RoCE 5-tuple flows.

Two committed scenarios anchor benchmarks and tests:

* ``paper_testbed_llm_workload`` — the job mapped onto the paper's
  16-server 2-rack testbed (every host its own "pod", so every
  cross-host ring edge is a DCN flow, like the RoCE cluster it models);
* ``multipod_llm_workload`` — the TPU adaptation: hosts grouped into
  pods, intra-pod edges ride the deterministic ICI torus and only
  pod-crossing edges enter the Clos fabric.

Feed the flows to ``simulate_paths(..., spec=SimSpec(demand_mode="bytes"))``
(or the Monte-Carlo front ends) to weight FIM and max-min throughput by
volume.

The uniform MoE slab of ``llm_collective_ops`` stays for the TPU
multi-pod scenario.  Skewed expert traffic comes from
``moe_dispatch_flows``: DeepSeek-V3's own group-limited router over
seeded, domain-skewed token affinities, and its expert-parallel dispatch
as RoCE flows from each GPU's own NIC.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .fabric import nic_ip, server_name
from .flows import (
    PROTO_UDP, ROCE_UDP_DPORT, FiveTuple, Flow, WorkloadDescription,
    workload_from_flows,
)
from .hlo_flows import (
    CollectiveOp, EdgeClassCounts, collectives_to_flows, wire_and_operand,
)
from .spans import count, span
from .timeline import TimelineStep, flow_channel, register_channel


@dataclasses.dataclass(frozen=True, slots=True)
class LlmJobSpec:
    """Shape of a data/expert-parallel LLM training step.

    ``num_hosts`` hosts of ``chips_per_host`` accelerators each.  The DP
    gradient sync runs one ring per chip index across all hosts (the
    standard multi-ring layout, so every host's NICs carry a share); the
    per-layer FSDP all-gather / reduce-scatter reuse those rings;
    expert-parallel all-to-all spans ``ep_group_hosts``-host groups; and
    one 4-byte barrier all-reduce models the control plane.

    Volumes derive from ``param_bytes`` (model size x dtype width) and
    the per-chip activation slab ``tokens_per_chip x hidden x
    dtype_bytes`` exactly like the HLO parser would report them.
    """

    num_hosts: int
    chips_per_host: int = 2
    hosts_per_pod: int | None = None   # None: every host its own pod
    param_bytes: int = 2_000_000_000   # ~1B params in bf16
    num_layers: int = 24
    moe_layers: int = 4
    ep_group_hosts: int = 8
    tokens_per_chip: int = 4096
    hidden: int = 4096
    dtype_bytes: int = 2


def _ring_op(kind: str, result_bytes: int, rings, channel_id: int,
             multiplier: int = 1) -> CollectiveOp:
    n = max((len(g) for g in rings), default=1)
    wire, operand = wire_and_operand(kind, result_bytes, n)
    return CollectiveOp(
        kind=kind, result_bytes=result_bytes, operand_bytes=operand,
        wire_bytes=wire, groups=tuple(tuple(g) for g in rings), pairs=(),
        channel_id=channel_id, line_no=0, multiplier=multiplier)


def llm_collective_ops(spec: LlmJobSpec) -> list[CollectiveOp]:
    """The per-step collective mix as ``CollectiveOp`` records.

    Byte model (per device, one step):

    * gradient all-reduce: each of the ``chips_per_host`` rings reduces
      its ``param_bytes / chips_per_host`` shard across all hosts;
    * FSDP all-gather + reduce-scatter: one layer's parameter shard per
      execution, ``num_layers`` executions (a while-loop trip count in
      real HLO);
    * MoE all-to-all: the ``tokens_per_chip x hidden`` activation slab
      shuffled across the EP group, once per MoE layer;
    * barrier: a 4-byte all-reduce across hosts (control plane).
    """
    h, cph = spec.num_hosts, spec.chips_per_host
    rings = [[host * cph + c for host in range(h)] for c in range(cph)]
    ep_span = max(1, min(spec.ep_group_hosts, h))
    ep_groups = [
        [host * cph + c for host in range(h0, min(h0 + ep_span, h))]
        for h0 in range(0, h, ep_span)
        for c in range(cph)
        if min(h0 + ep_span, h) - h0 > 1
    ]
    shard = spec.param_bytes // cph
    layer_shard = max(1, shard // spec.num_layers)
    a2a_bytes = spec.tokens_per_chip * spec.hidden * spec.dtype_bytes
    ops = [
        _ring_op("all-reduce", shard, rings, channel_id=1),
        _ring_op("all-gather", layer_shard, rings, channel_id=2,
                 multiplier=spec.num_layers),
        _ring_op("reduce-scatter", max(1, layer_shard // spec.num_hosts),
                 rings, channel_id=3, multiplier=spec.num_layers),
        _ring_op("all-reduce", 4, rings[:1], channel_id=5),   # barrier
    ]
    if spec.moe_layers > 0 and ep_groups:
        ops.insert(3, _ring_op("all-to-all", a2a_bytes, ep_groups,
                               channel_id=4, multiplier=spec.moe_layers))
    return ops


def llm_flows(
    spec: LlmJobSpec,
    *,
    host_name: "callable[[int], str] | None" = None,
) -> tuple[list[Flow], EdgeClassCounts]:
    """Decompose the job's collectives into DCN flows on a fabric.

    ``coords`` placement: device ``d`` lives on host ``d // chips_per
    host``; hosts are grouped ``hosts_per_pod`` to a pod, or — when
    ``hosts_per_pod`` is None — each host is its own pod, which makes
    every cross-host ring edge a DCN flow (the flat RoCE-cluster regime
    of the paper testbed).
    """
    cph = spec.chips_per_host
    coords = {}
    for d in range(spec.num_hosts * cph):
        host = d // cph
        pod = host if spec.hosts_per_pod is None else host // spec.hosts_per_pod
        coords[d] = (pod, host, d % cph)
    return collectives_to_flows(llm_collective_ops(spec), coords,
                                host_name=host_name)


def llm_workload(
    spec: LlmJobSpec,
    *,
    host_name: "callable[[int], str] | None" = None,
) -> tuple[WorkloadDescription, list[Flow], EdgeClassCounts]:
    """(byte-weighted workload description, concrete flows, edge stats)."""
    flows, stats = llm_flows(spec, host_name=host_name)
    return workload_from_flows(flows), flows, stats


def paper_testbed_llm_workload(
    **overrides,
) -> tuple[WorkloadDescription, list[Flow], EdgeClassCounts]:
    """The LLM job on the paper's 16-server testbed (``srv-i`` hosts).

    Every host is its own "pod" so all cross-host collective edges ride
    the 2-rack Clos — the heterogeneous sibling of the uniform 256-flow
    bipartite workload the paper saturates the fabric with.  Volumes
    span ~9 orders of magnitude (multi-GB all-reduce edges down to a
    7-byte barrier), which is exactly the regime where byte-weighted FIM
    diverges from unweighted FIM.
    """
    spec = LlmJobSpec(**{"num_hosts": 16, "hosts_per_pod": None,
                         **overrides})
    return llm_workload(spec, host_name=server_name)


def multipod_llm_workload(
    **overrides,
) -> tuple[WorkloadDescription, list[Flow], EdgeClassCounts]:
    """The LLM job across TPU pods (``host-i`` hosts of
    ``build_multipod_fabric``): intra-pod ring edges stay on ICI, only
    pod-crossing edges (DP ring seams + EP groups spanning pods) become
    DCN flows.  Defaults match the downscaled 2-pod x 8-host fabric the
    test suite uses."""
    spec = LlmJobSpec(**{"num_hosts": 16, "chips_per_host": 4,
                         "hosts_per_pod": 8, "ep_group_hosts": 16,
                         **overrides})
    return llm_workload(spec)


# ---------------------------------------------------------------------------
# Phase schedules (core/timeline.py)
# ---------------------------------------------------------------------------

#: channel map of ``llm_collective_ops``, the schedule vocabulary —
#: registered by name so schedule-validation errors print ``CH_*``
#: identifiers instead of bare ints (core/timeline.py registry)
CH_GRAD_AR = register_channel(1, "CH_GRAD_AR")
CH_FSDP_AG = register_channel(2, "CH_FSDP_AG")
CH_FSDP_RS = register_channel(3, "CH_FSDP_RS")
CH_MOE_A2A = register_channel(4, "CH_MOE_A2A")
CH_BARRIER = register_channel(5, "CH_BARRIER")

#: every collective runs alone, in training-step order — the synchronous
#: schedule of a vanilla FSDP/EP step (no comm/comm overlap)
SCHEDULE_SEQUENTIAL = "sequential"
#: gradient all-reduce overlapped into the backward phase (the standard
#: DP-overlap optimization), MoE shuffle overlapped with the forward
#: all-gather — two fat phases instead of four thin ones
SCHEDULE_DP_OVERLAP = "dp-overlap"


def llm_collective_phases(
    spec: LlmJobSpec, mode: str = SCHEDULE_SEQUENTIAL,
) -> tuple[list[CollectiveOp], list[TimelineStep]]:
    """Schedule-emitting variant of ``llm_collective_ops``: the same op
    list plus the ``TimelineStep`` schedule assigning each op's channel
    to a phase of the training step.

    ``"sequential"`` runs every collective in its own step — forward
    all-gather, MoE all-to-all, backward reduce-scatter, gradient
    all-reduce, barrier — which is what the merged snapshot mis-models
    hardest (it charges every phase the contention of all five).
    ``"dp-overlap"`` folds the gradient all-reduce into the backward
    phase and the MoE shuffle into the forward phase, the usual
    comm/comm overlap; the barrier stays its own (tiny) step.

    Steps carry equal default durations, read under ``timing="static"``
    (see core/timeline.py for why durations, not byte shares; under
    ``timing="event"`` durations are derived from the flows' byte
    volumes and the routed goodput instead).  Phases whose collective is
    absent from the spec (``moe_layers=0``) are dropped here — and
    ``llm_schedule`` additionally filters against the channels the
    *flows* actually carry, because ``simulate_timeline`` validates
    strictly and raises on a step no flow serves.
    """
    ops = llm_collective_ops(spec)
    if mode == SCHEDULE_SEQUENTIAL:
        schedule = [
            TimelineStep("fwd-all-gather", (CH_FSDP_AG,)),
            TimelineStep("moe-all-to-all", (CH_MOE_A2A,)),
            TimelineStep("bwd-reduce-scatter", (CH_FSDP_RS,)),
            TimelineStep("grad-all-reduce", (CH_GRAD_AR,)),
            TimelineStep("barrier", (CH_BARRIER,)),
        ]
    elif mode == SCHEDULE_DP_OVERLAP:
        schedule = [
            TimelineStep("forward", (CH_FSDP_AG, CH_MOE_A2A)),
            TimelineStep("backward", (CH_FSDP_RS, CH_GRAD_AR)),
            TimelineStep("barrier", (CH_BARRIER,)),
        ]
    else:
        raise ValueError(
            f"unknown schedule mode {mode!r}; expected "
            f"{SCHEDULE_SEQUENTIAL!r} or {SCHEDULE_DP_OVERLAP!r}")
    present = {op.channel_id for op in ops}
    schedule = [s for s in schedule
                if any(ch in present for ch in s.channels)]
    return ops, schedule


def llm_schedule(
    spec: LlmJobSpec,
    mode: str = SCHEDULE_SEQUENTIAL,
    *,
    host_name: "callable[[int], str] | None" = None,
) -> tuple[WorkloadDescription, list[Flow], EdgeClassCounts,
           list[TimelineStep]]:
    """Schedule-emitting variant of ``llm_workload``: the same
    (workload, flows, stats) triple plus the phase schedule, ready for
    ``simulate_timeline(fabric, flows, schedule, seeds)``.

    The schedule is filtered against the channels the emitted flows
    actually carry: a collective can be present in the op list yet
    produce zero DCN flows (e.g. a ring confined to one pod rides the
    ICI torus), and ``partition_flows`` rightly refuses a step no flow
    serves.  Each flow carries its byte volume (``Flow.bytes``), which
    is what gives ``timing="event"`` its per-step byte totals
    (``step_byte_totals``)."""
    _, schedule = llm_collective_phases(spec, mode)
    wl, flows, stats = llm_workload(spec, host_name=host_name)
    present = {flow_channel(f) for f in flows}
    schedule = [
        TimelineStep(s.name,
                     tuple(ch for ch in s.channels if ch in present),
                     s.duration)
        for s in schedule
        if any(ch in present for ch in s.channels)
    ]
    return wl, flows, stats, schedule


def paper_testbed_llm_schedule(
    mode: str = SCHEDULE_SEQUENTIAL, **overrides,
) -> tuple[WorkloadDescription, list[Flow], EdgeClassCounts,
           list[TimelineStep]]:
    """``paper_testbed_llm_workload`` plus its phase schedule."""
    spec = LlmJobSpec(**{"num_hosts": 16, "hosts_per_pod": None,
                         **overrides})
    return llm_schedule(spec, mode, host_name=server_name)


def multipod_llm_schedule(
    mode: str = SCHEDULE_SEQUENTIAL, **overrides,
) -> tuple[WorkloadDescription, list[Flow], EdgeClassCounts,
           list[TimelineStep]]:
    """``multipod_llm_workload`` plus its phase schedule."""
    spec = LlmJobSpec(**{"num_hosts": 16, "chips_per_host": 4,
                         "hosts_per_pod": 8, "ep_group_hosts": 16,
                         **overrides})
    return llm_schedule(spec, mode)


# ---------------------------------------------------------------------------
# DeepSeek-V3 expert-parallel dispatch (arXiv 2412.19437 §2.1.2, §3.2)
# ---------------------------------------------------------------------------

#: UDP source port of a GPU pair's first queue pair; the pair's
#: ``qps`` flows take consecutive ports from here
QP_BASE_PORT = 49152
#: values per FP8 quantization tile; each tile carries one float32 scale
FP8_TILE = 128


@dataclasses.dataclass(frozen=True, slots=True)
class MoeDispatchSpec:
    """One MoE layer's forward dispatch of an expert-parallel job.

    ``ep_groups`` groups of ``n_group`` nodes, each node a server of
    ``gpus_per_node`` GPUs with one single-port NIC per GPU; group ``e``
    holds servers ``e * n_group`` to ``e * n_group + n_group - 1``.  The
    router's group ``i`` is node ``i`` of the EP group, so its
    ``n_routed_experts / n_group`` experts live on that node's GPUs.
    Each GPU routes ``tokens_per_gpu`` tokens of one data domain.

    Router (DeepSeek-V3 ``noaux_tc``, sigmoid scoring, bias ``b = 0``):
    affinities ``s = sigmoid(logits)``; a group scores the sum of its
    top-2 ``s + b``; the top ``topk_group`` groups are kept and the top
    ``num_experts_per_tok`` experts by ``s + b`` among them are chosen.
    The logits of a token of domain ``d`` are ``log p_d(e) + N(0, 1)``,
    ``p_d`` Zipf with exponent ``zipf_skew`` over domain ``d``'s own
    permutation of the experts (``zipf_skew = 0`` is uniform); each GPU's
    domain is drawn uniformly from ``domains``.  Everything is drawn
    from ``seed``.

    Dispatch (DeepEP, node-limited): a token is sent once to every other
    node holding one of its experts, over the network to the GPU with
    the sender's in-node index, then over NVLink.  GPU ``g`` of node
    ``a`` sends ``c`` tokens to node ``b`` as ``qps`` RoCEv2 flows from
    NIC ``g`` of ``a`` to NIC ``g`` of ``b``, each of
    ``c * token_bytes / qps`` bytes.
    """

    ep_groups: int = 1
    n_group: int = 8
    gpus_per_node: int = 8
    tokens_per_gpu: int = 4096
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    topk_group: int = 4
    hidden_size: int = 7168
    domains: int = 8
    zipf_skew: float = 0.99
    qps: int = 16
    seed: int = 0

    def __post_init__(self):
        if self.n_routed_experts % self.n_group:
            raise ValueError(f"{self.n_routed_experts} experts do not "
                             f"split into {self.n_group} groups")
        per_group = self.n_routed_experts // self.n_group
        if per_group < 2:
            raise ValueError("a group scores its top 2 experts")
        if not (1 <= self.topk_group <= self.n_group
                and 1 <= self.num_experts_per_tok
                <= self.topk_group * per_group):
            raise ValueError(
                f"top {self.num_experts_per_tok} experts of top "
                f"{self.topk_group} groups of {self.n_group} x "
                f"{per_group} cannot be chosen")
        if self.token_bytes % self.qps:
            raise ValueError(f"{self.token_bytes} bytes a token do not "
                             f"split over {self.qps} queue pairs")

    @property
    def token_bytes(self) -> int:
        """Wire bytes of one dispatched token: ``hidden_size`` FP8
        values and one float32 scale per ``FP8_TILE`` values."""
        return self.hidden_size + -(-self.hidden_size // FP8_TILE) * 4


def _rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *key]))


def expert_popularity(spec: MoeDispatchSpec) -> np.ndarray:
    """(domains, n_routed_experts) ``p_d(e)``: Zipf(``zipf_skew``) over
    each domain's own permutation of the experts."""
    rng = _rng(spec.seed, 0)
    E = spec.n_routed_experts
    zipf = np.arange(1, E + 1, dtype=np.float64) ** -spec.zipf_skew
    zipf /= zipf.sum()
    out = np.empty((spec.domains, E))
    for d in range(spec.domains):
        out[d, rng.permutation(E)] = zipf
    return out


def source_domains(spec: MoeDispatchSpec) -> np.ndarray:
    """(ep_groups, n_group, gpus_per_node) domain of each GPU's tokens."""
    return _rng(spec.seed, 1).integers(
        0, spec.domains, (spec.ep_groups, spec.n_group, spec.gpus_per_node))


def router_logits(spec: MoeDispatchSpec, group: int, node: int) -> np.ndarray:
    """(gpus_per_node, tokens_per_gpu, n_routed_experts) float64 router
    logits of one node's tokens, ``log p_d(e) + N(0, 1)``."""
    p = expert_popularity(spec)[source_domains(spec)[group, node]]
    noise = _rng(spec.seed, 2, group, node).standard_normal(
        (spec.gpus_per_node, spec.tokens_per_gpu, spec.n_routed_experts))
    return np.log(p)[:, None, :] + noise


def route_tokens(logits: np.ndarray, *, n_group: int, topk_group: int,
                 top_k: int) -> tuple[np.ndarray, np.ndarray]:
    """DeepSeek-V3's group-limited top-k over the last axis of
    ``logits``, bias 0: ``(experts, groups)``, the ``top_k`` chosen
    experts and the ``topk_group`` kept groups of each token, each in
    ascending order.  The gate values are not computed."""
    score = 1.0 / (1.0 + np.exp(-logits))
    E = score.shape[-1]
    grouped = score.reshape(*score.shape[:-1], n_group, E // n_group)
    per = grouped.shape[-1]
    group_score = np.partition(grouped, per - 2, axis=-1)[..., -2:].sum(-1)
    groups = np.argpartition(-group_score, topk_group - 1,
                             axis=-1)[..., :topk_group]
    kept = np.take_along_axis(grouped, groups[..., None], axis=-2)
    kept = kept.reshape(*kept.shape[:-2], topk_group * per)
    pick = np.argpartition(-kept, top_k - 1, axis=-1)[..., :top_k]
    experts = np.take_along_axis(groups, pick // per, axis=-1) * per + (
        pick % per)
    return np.sort(experts, axis=-1), np.sort(groups, axis=-1)


def dispatch_counts(spec: MoeDispatchSpec) -> np.ndarray:
    """(ep_groups, n_group, gpus_per_node, n_group) int64 tokens GPU
    ``g`` of node ``a`` dispatches over the network to node ``b``
    (``[e, a, g, b]``); 0 where ``b == a``, which NVLink serves.  A
    token counts once for every node holding one of its experts."""
    per_node = spec.n_routed_experts // spec.n_group
    out = np.zeros((spec.ep_groups, spec.n_group, spec.gpus_per_node,
                    spec.n_group), np.int64)
    for e in range(spec.ep_groups):
        for a in range(spec.n_group):
            experts, _ = route_tokens(
                router_logits(spec, e, a), n_group=spec.n_group,
                topk_group=spec.topk_group, top_k=spec.num_experts_per_tok)
            hit = np.zeros((*experts.shape[:-1], spec.n_group), bool)
            np.put_along_axis(hit, experts // per_node, True, axis=-1)
            out[e, a] = hit.sum(axis=1)
            out[e, a, :, a] = 0
    return out


def moe_dispatch_flows(spec: MoeDispatchSpec,
                       counts: np.ndarray | None = None) -> list[Flow]:
    """The dispatch as RoCEv2 flows between ``srv-<i>`` servers, in
    ``(group, node, gpu, node, queue pair)`` order, inside the
    ``moe.dispatch`` span (counters ``tokens`` routed, ``pairs`` of
    (GPU, node) with traffic and ``flows``).  ``counts`` defaults to
    ``dispatch_counts(spec)``; a pair with no tokens emits no flow."""
    with span("moe.dispatch"):
        c = dispatch_counts(spec) if counts is None else np.asarray(counts)
        per_token = spec.token_bytes // spec.qps
        label = f"moe-dispatch#ch{CH_MOE_A2A}"
        out: list[Flow] = []
        for e, a, g, b in zip(*np.nonzero(c)):
            if a == b:
                raise ValueError(f"group {e}: node {a} dispatches to itself")
            src = server_name(int(e * spec.n_group + a))
            dst = server_name(int(e * spec.n_group + b))
            src_ip, dst_ip = nic_ip(src, int(g)), nic_ip(dst, int(g))
            size, first = int(c[e, a, g, b]) * per_token, len(out)
            out.extend(
                Flow(flow_id=first + q, src=src, dst=dst,
                     tuple5=FiveTuple(src_ip, dst_ip, QP_BASE_PORT + q,
                                      ROCE_UDP_DPORT, PROTO_UDP),
                     bytes=size, label=label)
                for q in range(spec.qps))
        count("tokens", spec.ep_groups * spec.n_group * spec.gpus_per_node
              * spec.tokens_per_gpu)
        count("pairs", int(np.count_nonzero(c)))
        count("flows", len(out))
    return out
