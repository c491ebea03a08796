"""Freeze each configuration's inputs into data files.

    PYTHONPATH=src python chipbench/freeze.py [--check]

Writes ``configs/<config>/fabric.json`` and ``flows.json`` from the
program's own builders, once.  The
benchmark then rebuilds every input from these files alone, so a later
change to a builder (``build_paper_testbed``, ``bipartite_pairs``,
``synthesize_flows``) cannot move the
yardstick.  ``--check`` rewrites nothing and exits 1 if a committed file
differs from what the builders give now.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CONFIGS = HERE / "configs"


def _lines(key: str, rows: list[dict]) -> str:
    body = ",\n".join(json.dumps(r, separators=(", ", ": ")) for r in rows)
    return f'"{key}": [\n{body}\n]'


def fabric_json(fabric) -> str:
    return ("{" + _lines("devices", [dataclasses.asdict(d)
                                     for d in fabric.devices.values()])
            + ",\n" + _lines("links", [dataclasses.asdict(ln)
                                       for ln in fabric.links]) + "}\n")


def flows_json(flows) -> str:
    rows = [{"flow_id": f.flow_id, "src": f.src, "dst": f.dst,
             "src_ip": f.tuple5.src_ip, "dst_ip": f.tuple5.dst_ip,
             "src_port": f.tuple5.src_port, "dst_port": f.tuple5.dst_port,
             "protocol": f.tuple5.protocol, "bytes": f.bytes,
             "label": f.label} for f in flows]
    return "{" + _lines("flows", rows) + "}\n"


def frozen() -> dict[str, dict[str, str]]:
    """{config name: {file name: text}} from this tree's builders."""
    from repro.core import (
        bipartite_pairs, build_paper_testbed, nic_ip, server_name,
        synthesize_flows,
    )

    # the paper's Fig. 2b pattern: server i of rack 0 <-> server i of
    # rack 1, both directions, 16 flows per directed pair
    wl = bipartite_pairs([server_name(i) for i in range(8)],
                         [server_name(8 + i) for i in range(8)],
                         flows_per_pair=16)
    paper = synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)
    return {
        "paper-testbed": {
            "fabric.json": fabric_json(build_paper_testbed()),
            "flows.json": flows_json(paper),
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--check", action="store_true",
                    help="compare with the committed files, write nothing")
    args = ap.parse_args(argv)
    stale = []
    for config, files in frozen().items():
        for name, text in files.items():
            path = CONFIGS / config / name
            if args.check:
                if not path.exists() or path.read_text() != text:
                    stale.append(str(path.relative_to(HERE)))
                continue
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(text)
    if stale:
        print(f"freeze: differs from the builders: {stale}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
