"""Two pods of Meta's 24K-GPU RoCE cluster (arXiv 2407.21783 §3.3.1)
from the program's public builders, frozen into ``fabric.json`` and
``flows.json`` by ``chipbench/freeze.py``."""

from repro.core import FiveTuple, Flow, build_three_tier_clos, nic_ip, server_name

PODS, RACKS_PER_POD, SERVERS_PER_RACK, GPUS_PER_SERVER = 2, 42, 2, 8
FLOWS_PER_GPU_PAIR = 16              # the 16 QPs between two GPUs


def fabric():
    """42 racks a pod, one ToR each over 2 servers x 8 single-port
    400G NICs; 16 cluster switches a pod, one link from every ToR to
    each; 16 aggregation planes of 2 switches, each cluster switch 3
    links to each of its plane's 2 (42 down : 6 up = 1:7)."""
    return build_three_tier_clos(
        num_pods=PODS, racks_per_pod=RACKS_PER_POD,
        servers_per_rack=SERVERS_PER_RACK, nics_per_server=GPUS_PER_SERVER,
        cluster_switches=16, aggs_per_plane=2, uplinks=6, link_gbps=400.0)


def flows():
    """The cross-pod step of a hierarchical data-parallel all-reduce:
    GPU g of server i in pod 0 and GPU g of server i in pod 1 exchange
    16 RoCEv2 flows each way, one per UDP source port 49152-49167."""
    per_pod = RACKS_PER_POD * SERVERS_PER_RACK
    out = []
    for i in range(per_pod):
        a, b = server_name(i), server_name(per_pod + i)
        for g in range(GPUS_PER_SERVER):
            for src, dst in ((a, b), (b, a)):
                for q in range(FLOWS_PER_GPU_PAIR):
                    out.append(Flow(
                        flow_id=len(out), src=src, dst=dst,
                        tuple5=FiveTuple(nic_ip(src, g), nic_ip(dst, g),
                                         49152 + q, 4791, 17),
                        bytes=0, label=f"dp-xpod gpu{g}"))
    return out
