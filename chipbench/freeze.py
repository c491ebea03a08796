"""Freeze each configuration's inputs into data files.

    python chipbench/freeze.py [--check]

Every configuration is a directory ``configs/<config>/`` with its
``config.json`` and a ``build.py`` whose ``fabric()`` and ``flows()``
call the program's public builders.  This writes ``fabric.json`` and
``flows.json`` beside them, once.  The benchmark then rebuilds every
input from these files alone, so a later change to a builder cannot
move the yardstick.  ``--check`` rewrites nothing and exits 1 if a
committed file differs from what the builders give now.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
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


def builder(config_dir: Path):
    """The configuration's ``build.py`` as a module."""
    path = config_dir / "build.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_build_{config_dir.name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def frozen() -> dict[str, dict[str, str]]:
    """{config name: {file name: text}} from this tree's builders, for
    every ``configs/*/build.py``."""
    out = {}
    for path in sorted(CONFIGS.glob("*/build.py")):
        mod = builder(path.parent)
        out[path.parent.name] = {"fabric.json": fabric_json(mod.fabric()),
                                 "flows.json": flows_json(mod.flows())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--check", action="store_true",
                    help="compare with the committed files, write nothing")
    args = ap.parse_args(argv)
    src = str(HERE.parent / "src")              # the program's builders
    if src not in sys.path:
        sys.path.insert(0, src)
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
