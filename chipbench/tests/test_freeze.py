"""The frozen inputs: reproduced by each configuration's builder byte for
byte, and enough on their own to build each cell's program inputs."""

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


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_is_a_directory_of_its_own(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    path = HERE / "configs" / config
    assert (HERE.parent / entry["file"]) == path / "config.json"
    assert {"config.json", "build.py", "fabric.json", "flows.json"} <= {
        p.name for p in path.iterdir()}
    assert config in freeze.frozen()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_inputs_build_from_data_alone(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    traffic = json.loads(
        (HERE / "traffic" / f"{entry['traffic']}.json").read_text())
    config_dir = HERE / "configs" / entry["config"]
    fam = frontends.family(config_dir, traffic).build()
    build = freeze.builder(config_dir)
    fabric = build.fabric()
    assert fam.comp.links == fabric.links
    assert list(fam.comp.fabric.devices.values()) == list(
        fabric.devices.values())
    assert fam.flows == build.flows()
