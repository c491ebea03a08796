"""DeepSeek-V3's expert-parallel dispatch (arXiv 2412.19437 §3.2) on one
pod of Meta's RoCE cluster (arXiv 2407.21783 §3.3.1), from the
program's public builders, frozen into ``fabric.json`` and
``flows.json`` by ``chipbench/freeze.py``."""

from repro.core import MoeDispatchSpec, build_three_tier_clos, moe_dispatch_flows

RACKS, SERVERS_PER_RACK, GPUS_PER_SERVER = 48, 2, 8
EP_NODES = 8                         # EP-64: 8 nodes of 8 GPUs
SEED = 19437                         # router logits, domains, popularity


def fabric():
    """One pod: 48 racks, one ToR each over 2 servers x 8 single-port
    400G NICs; 16 cluster switches, one link from every ToR to each; 16
    aggregation planes of 2 switches, each cluster switch 3 links to
    each of its plane's 2."""
    return build_three_tier_clos(
        num_pods=1, racks_per_pod=RACKS, servers_per_rack=SERVERS_PER_RACK,
        nics_per_server=GPUS_PER_SERVER, cluster_switches=16,
        aggs_per_plane=2, uplinks=6, link_gbps=400.0)


def flows():
    """One MoE layer's forward dispatch of 12 EP-64 groups, each 8
    consecutive servers (4 whole racks): DeepSeek-V3's router over
    4096 tokens a GPU, each GPU's tokens from one Zipf(0.99) data
    domain; GPU g of node a sends its tokens for node b as 16 RoCEv2
    flows from its own NIC to NIC g of b, 7392 bytes a token."""
    spec = MoeDispatchSpec(
        ep_groups=RACKS * SERVERS_PER_RACK // EP_NODES, n_group=EP_NODES,
        gpus_per_node=GPUS_PER_SERVER, tokens_per_gpu=4096,
        n_routed_experts=256, num_experts_per_tok=8, topk_group=4,
        hidden_size=7168, domains=8, zipf_skew=0.99, qps=16, seed=SEED)
    return moe_dispatch_flows(spec)
