"""The frozen inputs: reproduced by this tree's builders byte for byte,
and enough on their own to build each cell's program inputs."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import freeze, frontends

HERE = Path(freeze.__file__).resolve().parent
BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())


def test_freeze_reproduces_committed_files():
    for config, files in freeze.frozen().items():
        for name, text in files.items():
            assert (HERE / "configs" / config / name).read_text() == text, (
                f"{config}/{name} differs from the builders")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_inputs_build_from_data_alone(cell):
    from repro.core import (
        bipartite_pairs, build_paper_testbed, nic_ip, server_name,
        synthesize_flows,
    )

    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    traffic = json.loads(
        (HERE / "traffic" / f"{entry['traffic']}.json").read_text())
    fam = frontends.family(HERE / "configs" / entry["config"],
                           traffic).build()
    assert entry["config"] == "paper-testbed"
    fabric = build_paper_testbed()
    flows = synthesize_flows(
        bipartite_pairs([server_name(i) for i in range(8)],
                        [server_name(8 + i) for i in range(8)],
                        flows_per_pair=16),
        nic_ip=nic_ip, nics_per_server=2)
    assert fam.comp.links == fabric.links
    assert list(fam.comp.fabric.devices.values()) == list(
        fabric.devices.values())
    assert fam.flows == flows
