"""Readings that set the limits of ``correct``.

    python chipbench/calibrate.py --workload <cell> --seeds 12 --controls 3

Lower readings: the program's own answers, one full call at the cell's
size per seed, compared with the float64 reference over the same sample
a run compares.  Upper readings: the control, the reference put in the
program's place one precision below what the configuration states
(float32; bfloat16 for the FIM, which float32 computes exactly), over
the same samples.  Prints one JSON line per reading.  The lower readings
need the chip, like a run; the controls are numpy and need none
(``--seeds 0``).  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent / "src"))

from chipbench import frontends  # noqa: E402
from chipbench import run as R  # noqa: E402

CONTROL = {"monte_carlo_throughput": "float32", "monte_carlo_fim": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    args = ap.parse_args(argv)
    import ml_dtypes
    import numpy as np

    cell = R.Cell(R.load_json(R.ROOT / "BENCHMARK.json"), args.workload)
    fam = frontends.family(cell.config_dir, cell.traffic)
    if args.seeds:
        jax, _ = R.configure_jax()
        R.check_device(jax, cell.chips)
        fam.build()
    front = cell.traffic["front_end"]
    dtype = {"float32": np.float32,
             "bfloat16": ml_dtypes.bfloat16}[CONTROL[front]]
    for i in range(max(args.seeds, args.controls)):
        seed = args.first_seed + i
        seeds = R.seeds_for(seed, 0, fam.seeds_per_call)
        rows = []
        if i < args.seeds:
            rows.append(("program", [fam.call(seeds)]))
        if i < args.controls:
            rows.append((f"control-{CONTROL[front]}",
                         [fam.reference(seeds, dtype=dtype)]))
        for who, answers in rows:
            numbers = R.check(fam, answers, [seeds], seed)
            print(json.dumps({"cell": args.workload, "seed": seed,
                              "who": who, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
