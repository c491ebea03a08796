"""FlowTracer core: the paper's primary contribution.

Fabric model + ECMP/static routing + Flow Imbalance Metric + the parallel
hop-by-hop path-discovery algorithm + compiled-HLO flow extraction +
topology-aware placement.  Importing the package stays jax-free so tracer
worker processes remain lightweight: the device engine
(``core.jax_engine``, selected via ``engine="jax"`` on the Monte-Carlo
front ends) imports jax lazily, only when actually asked to run.
"""

from .fabric import (
    Fabric, Link, Device, build_paper_testbed, build_multipod_fabric,
    build_three_tier_clos, nic_ip, server_name,
    HOST_TO_LEAF, LEAF_TO_SPINE, SPINE_TO_LEAF, LEAF_TO_HOST,
    SPINE_TO_AGG, AGG_TO_SPINE,
)
from .flows import (
    Flow, FiveTuple, PairSpec, WorkloadDescription, synthesize_flows,
    bipartite_pairs, workload_from_flows,
)
from .ecmp import (
    EcmpRouting, StaticRouting, RoutingPolicy, Forwarder, ecmp_hash,
    device_seed, flow_hash_fields, flow_fields_matrix,
    FIELDS_5TUPLE, FIELDS_VXLAN, FIELDS_IP_PAIR,
)
from .compile_fabric import CompiledFabric, compile_fabric
from .contracts import CONTRACTS_ENV, ContractViolation, contracts_enabled
from .vector_sim import (
    VectorTraceResult, MonteCarloFim, SimSpec, simulate_paths,
    fim_from_counts, fim_vector, monte_carlo_fim, resolve_flows,
    DEMAND_UNIFORM, DEMAND_BYTES, flow_demand_weights,
    ENGINE_NUMPY, ENGINE_JAX, resolve_hash_backend,
    TIMING_STATIC, TIMING_EVENT,
)
from .vector_throughput import (
    MonteCarloThroughput, batched_max_min, max_min_rates,
    flow_rates_from_flowlets, pair_rate_matrix, throughput_from_result,
    monte_carlo_throughput, DepartureFill, departure_fill,
)
from .strategies import (
    RoutingStrategy, EcmpStrategy, PrimeSpraying, AdaptiveSpraying,
    CongestionAware, WaveCongestionAware,
    register_strategy, resolve_strategy, available_strategies,
    ELEPHANT_MIN_BYTES,
)
from .reordering import (
    TransportProfile, IDEAL, ROCE_NACK, STRACK,
    ROCE_NACK_ANCHORS, STRACK_ANCHORS, calibrate_transport,
    register_transport, resolve_transport, available_transports,
    flowlet_exposure, reordering_efficiency,
    DEFAULT_RTT_SECONDS, rtt_round_budget,
)
from .timeline import (
    TimelineStep, TimelineResult, StepResult, simulate_timeline,
    merged_step, partition_flows, flow_channel,
    register_channel, known_channels, channel_name, step_byte_totals,
)
from .fim import (
    fim, per_layer_fim, link_flow_counts, max_min_throughput,
    per_pair_throughput, layer_load_stats, LayerLoadStats,
)
from .tracer import (
    FlowTracer, TraceResult, LatencyModel, ConnectionManager, DeviceChannel,
    ADHOC, PERSISTENT, auto_processes,
)
from .hlo_flows import (
    CollectiveOp, extract_collectives, summarize, collectives_to_flows,
    shape_bytes, CollectiveSummary, EdgeClassCounts, wire_and_operand,
)
from .llm_workload import (
    LlmJobSpec, llm_collective_ops, llm_flows, llm_workload,
    paper_testbed_llm_workload, multipod_llm_workload,
    llm_collective_phases, llm_schedule,
    paper_testbed_llm_schedule, multipod_llm_schedule,
    SCHEDULE_SEQUENTIAL, SCHEDULE_DP_OVERLAP,
    CH_GRAD_AR, CH_FSDP_AG, CH_FSDP_RS, CH_MOE_A2A, CH_BARRIER,
)
from .placement import (
    static_route_assignment, topology_aware_ring, ring_edge_stats,
    balanced_port_spread,
)
from .report import analyze_paths, PathReport

__all__ = [
    "Fabric", "Link", "Device", "build_paper_testbed", "build_multipod_fabric",
    "build_three_tier_clos", "nic_ip", "server_name",
    "HOST_TO_LEAF", "LEAF_TO_SPINE", "SPINE_TO_LEAF", "LEAF_TO_HOST",
    "SPINE_TO_AGG", "AGG_TO_SPINE",
    "Flow", "FiveTuple", "PairSpec", "WorkloadDescription", "synthesize_flows",
    "bipartite_pairs", "workload_from_flows",
    "EcmpRouting", "StaticRouting", "RoutingPolicy", "Forwarder", "ecmp_hash",
    "device_seed", "flow_hash_fields", "flow_fields_matrix",
    "FIELDS_5TUPLE", "FIELDS_VXLAN", "FIELDS_IP_PAIR",
    "CompiledFabric", "compile_fabric",
    "CONTRACTS_ENV", "ContractViolation", "contracts_enabled",
    "VectorTraceResult", "MonteCarloFim", "SimSpec", "simulate_paths",
    "fim_from_counts", "fim_vector", "monte_carlo_fim", "resolve_flows",
    "DEMAND_UNIFORM", "DEMAND_BYTES", "flow_demand_weights",
    "ENGINE_NUMPY", "ENGINE_JAX", "resolve_hash_backend",
    "TIMING_STATIC", "TIMING_EVENT",
    "MonteCarloThroughput", "batched_max_min", "max_min_rates",
    "flow_rates_from_flowlets", "pair_rate_matrix", "throughput_from_result",
    "monte_carlo_throughput", "DepartureFill", "departure_fill",
    "RoutingStrategy", "EcmpStrategy", "PrimeSpraying", "AdaptiveSpraying",
    "CongestionAware", "WaveCongestionAware",
    "register_strategy", "resolve_strategy", "available_strategies",
    "ELEPHANT_MIN_BYTES",
    "TransportProfile", "IDEAL", "ROCE_NACK", "STRACK",
    "ROCE_NACK_ANCHORS", "STRACK_ANCHORS", "calibrate_transport",
    "register_transport", "resolve_transport", "available_transports",
    "flowlet_exposure", "reordering_efficiency",
    "DEFAULT_RTT_SECONDS", "rtt_round_budget",
    "TimelineStep", "TimelineResult", "StepResult", "simulate_timeline",
    "merged_step", "partition_flows", "flow_channel",
    "register_channel", "known_channels", "channel_name", "step_byte_totals",
    "fim", "per_layer_fim", "link_flow_counts", "max_min_throughput",
    "per_pair_throughput", "layer_load_stats", "LayerLoadStats",
    "FlowTracer", "TraceResult", "LatencyModel", "ConnectionManager",
    "DeviceChannel", "ADHOC", "PERSISTENT", "auto_processes",
    "CollectiveOp", "extract_collectives", "summarize", "collectives_to_flows",
    "shape_bytes", "CollectiveSummary", "EdgeClassCounts", "wire_and_operand",
    "LlmJobSpec", "llm_collective_ops", "llm_flows", "llm_workload",
    "paper_testbed_llm_workload", "multipod_llm_workload",
    "llm_collective_phases", "llm_schedule",
    "paper_testbed_llm_schedule", "multipod_llm_schedule",
    "SCHEDULE_SEQUENTIAL", "SCHEDULE_DP_OVERLAP",
    "CH_GRAD_AR", "CH_FSDP_AG", "CH_FSDP_RS", "CH_MOE_A2A", "CH_BARRIER",
    "static_route_assignment", "topology_aware_ring", "ring_edge_stats",
    "balanced_port_spread",
    "analyze_paths", "PathReport",
]
