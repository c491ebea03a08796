"""The paper testbed's inputs from the program's public builders, frozen
into ``fabric.json`` and ``flows.json`` by ``chipbench/freeze.py``."""

from repro.core import (
    bipartite_pairs, build_paper_testbed, nic_ip, server_name,
    synthesize_flows,
)


def fabric():
    """Fig. 2a: 2 racks x 8 servers, 4 leaves, 4 spines x 4 links."""
    return build_paper_testbed()


def flows():
    """Fig. 2b: server i of rack 0 <-> server i of rack 1, both
    directions, 16 flows per directed pair."""
    wl = bipartite_pairs([server_name(i) for i in range(8)],
                         [server_name(8 + i) for i in range(8)],
                         flows_per_pair=16)
    return synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)
