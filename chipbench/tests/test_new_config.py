"""A configuration is new files only: a checkout that gains a synthetic
two-tier configuration, a traffic mix and a cell, and changes no file the
benchmark has, freezes the configuration and runs the cell correct."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

from chipbench import run as R

BUILD = '''\
"""Three racks of four servers, one single-port NIC per leaf, three
spines of two links each; rack 0 and rack 2 exchange four flows per
server pair, with volumes that differ per pair."""

from repro.core import (
    bipartite_pairs, build_paper_testbed, nic_ip, server_name,
    synthesize_flows,
)


def fabric():
    return build_paper_testbed(num_racks=3, servers_per_rack=4,
                               leaves_per_rack=2, num_spines=3,
                               links_per_leaf_spine=2, link_gbps=200.0,
                               ports_per_nic=1)


def flows():
    wl = bipartite_pairs([server_name(i) for i in range(4)],
                         [server_name(8 + i) for i in range(4)],
                         flows_per_pair=4,
                         bytes_per_flow=[1 << 20, 3 << 20, 5 << 20, 7 << 20])
    return synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)
'''

TRAFFIC = {
    "why": "A CPU-sized throughput mix weighted by flow volume.",
    "front_end": "monte_carlo_throughput",
    "spec": {"hash_backend": "murmur", "demand_mode": "bytes",
             "transport": "roce-nack"},
    "seeds_per_call": 64, "pool_seed": 3, "order_block": 4,
    "metric": "sweep_cells_per_s", "trace_calls": 1, "reference": {},
    "check": {"seeds": 64, "limits": {"rate_gap": 1e-9,
                                      "goodput_gap": 1e-9}},
}

#: runs one cell of the checkout in the current directory with the
#: harness's look for a chip stubbed out; the program comes from argv[1]
DRIVE = textwrap.dedent('''\
    import argparse, json, sys
    sys.path[:0] = [".", sys.argv[1]]
    from chipbench import run as R
    R.check_device = lambda jax, chips: (
        jax.devices()[0], R.load_json(R.HERE / "peaks.json")["TPU v5 lite"])
    result, _ = R.run(argparse.Namespace(
        workload=sys.argv[2], seed=2**31 + 16, seconds=0.01, trace=0))
    print(json.dumps(result))
''')


def _digests(root) -> dict[str, str]:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_configuration_is_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(R.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    had = _digests(root / "chipbench")

    name = "three-rack-two-tier"
    cfg = root / "chipbench" / "configs" / name
    cfg.mkdir()
    (cfg / "build.py").write_text(BUILD)
    (cfg / "config.json").write_text(json.dumps({"name": name}))
    (root / "chipbench" / "traffic" / "tiny-bytes.json").write_text(
        json.dumps(TRAFFIC))
    bench["configs"].append(
        {"name": name, "source": "synthetic",
         "file": f"chipbench/configs/{name}/config.json", "reduced": [],
         "why": "a second two-tier fabric"})
    bench["workloads"].append(
        {"name": f"{name}-bytes", "config": name, "traffic": "tiny-bytes",
         "chips": 1, "why": "a CPU-sized cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env |= {"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(R.ROOT / "src")}
    for argv in (["chipbench/freeze.py"], ["chipbench/freeze.py", "--check"]):
        subprocess.run([sys.executable, *argv], cwd=root, env=env,
                       check=True, timeout=300)
    assert {"fabric.json", "flows.json"} <= {p.name for p in cfg.iterdir()}

    proc = subprocess.run(
        [sys.executable, "-c", DRIVE, str(R.ROOT / "src"), f"{name}-bytes"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"], result["check"]
    assert result["attempted"] >= 1 and result["failed"] == 0

    now = _digests(root / "chipbench")
    assert {k: now[k] for k in had} == had
