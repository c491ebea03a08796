"""The front-end families a traffic mix can drive, read from data.

A traffic file (``traffic/<mix>.json``) names its ``front_end``, the
``spec`` fields handed to the program's ``SimSpec``, the hash seeds per
call, the end-to-end metric it reports, and how many answers the check
samples against what limits.  Each family here

* builds the program's inputs from a configuration's frozen files
  through the public constructors (``Fabric``/``Device``/``Link``,
  ``Flow``/``FiveTuple``) and ``compile_fabric``;
* calls the front end with ``engine="jax"`` and brings its answers to
  the host;
* lists the device kernels one call runs, with their shapes, for the
  per-layer readers;
* compares a sample of answers with ``reference.py`` and returns each
  number compared.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

from chipbench import reference as ref


def _load(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def rel_gap(a: np.ndarray, b: np.ndarray) -> float:
    """Largest |a - b| / |b| (0 where both are 0; inf on a shape or
    non-finite mismatch)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape or not np.isfinite(a).all():
        return float("inf")
    den = np.abs(b)
    gap = np.abs(a - b)
    if (gap[den == 0] > 0).any():
        return float("inf")
    return float(np.max(gap[den > 0] / den[den > 0], initial=0.0))


@dataclasses.dataclass
class Kernel:
    """One device-kernel invocation of a call: the jitted stage's name and
    the sizes its least bytes are computed from."""

    stage: str
    sizes: dict


class Family:
    """Shared loading and comparison.  Subclasses implement ``call`` (the
    program's answers as a dict of arrays with the seeds on the last
    axis), ``reference`` (the same dict from ``reference.py`` at a given
    precision), ``gaps`` (each number compared), ``columns`` and
    ``kernels``."""

    def __init__(self, config_dir: Path, traffic: dict):
        self.traffic = traffic
        self.spec_fields = dict(traffic["spec"])
        self.seeds_per_call = int(traffic["seeds_per_call"])
        self.fabric_json = _load(config_dir / "fabric.json")
        self.flows_json = _load(config_dir / "flows.json")["flows"]
        self.topo = ref.Topology(self.fabric_json)

    # -- program inputs, rebuilt from data --------------------------------
    def build(self):
        from repro.core import Device, Fabric, FiveTuple, Flow, Link, SimSpec
        from repro.core import compile_fabric

        fab = Fabric([Device(**d) for d in self.fabric_json["devices"]],
                     [Link(**ln) for ln in self.fabric_json["links"]])
        self.comp = compile_fabric(fab)
        self.flows = [
            Flow(flow_id=f["flow_id"], src=f["src"], dst=f["dst"],
                 tuple5=FiveTuple(f["src_ip"], f["dst_ip"], f["src_port"],
                                  f["dst_port"], f["protocol"]),
                 bytes=f["bytes"], label=f["label"])
            for f in self.flows_json]
        self.spec = SimSpec(engine="jax", **self.spec_fields)
        return self

    def compare(self, answers, seeds) -> dict[str, float]:
        """Each number compared between the program's answers for
        ``seeds`` and the float64 reference."""
        return self.gaps(answers, self.reference(seeds))

    @property
    def cells_per_call(self) -> int:
        return len(self.flows_json) * self.seeds_per_call

    def table_bytes(self) -> int:
        """Bytes of the forwarding tables the walk reads: candidate
        links and counts per (device, NIC), per-device hash salt and
        server flag, per-link destination.  The candidates are the
        reference's own, padded to the largest set."""
        topo = self.topo
        V, K, L = len(topo.names), len(topo.nics), topo.num_links
        C = topo.largest_fanout()
        return V * K * C * 4 + V * K * 4 + V * 8 + V + L * 4

    def hops(self, seeds: np.ndarray) -> int:
        """Real hop count of a call: the longest reference path."""
        return ref.route(self.topo, self.flows_json, seeds[:256]).shape[0]


class Throughput(Family):
    """``monte_carlo_throughput`` on plain ECMP: walk and fill fused."""

    def call(self, seeds):
        from repro.core import monte_carlo_throughput
        out = monte_carlo_throughput(self.comp, self.flows, seeds,
                                     spec=self.spec)
        return {"rates": np.asarray(out.rates),
                "goodput": np.asarray(out.goodput)}

    def kernels(self, seeds):
        H, N, S = self.hops(seeds), len(self.flows_json), len(seeds)
        L = self.topo.num_links
        return [Kernel("walk", dict(H=H, N=N, S=S, F=5,
                                    tables=self.table_bytes())),
                Kernel("fill", dict(H=H, N=N, S=S, L=L))]

    def reference(self, seeds, dtype=np.float64):
        r = ref.throughput(self.topo, self.flows_json, seeds,
                           demand=self.spec_fields.get("demand_mode",
                                                       "uniform"),
                           dtype=dtype)
        return {"rates": r, "goodput": r}

    def gaps(self, answers, want):
        return {"rate_gap": rel_gap(answers["rates"], want["rates"]),
                "goodput_gap": rel_gap(answers["goodput"], want["goodput"])}

    def columns(self, answers, idx):
        return {k: v[:, idx] for k, v in answers.items()}


class Fim(Family):
    """``monte_carlo_fim`` on plain ECMP: walk, counts and FIM fused."""

    def call(self, seeds):
        from repro.core import monte_carlo_fim
        out = monte_carlo_fim(self.comp, self.flows, seeds, spec=self.spec)
        return {"aggregate": np.asarray(out.aggregate),
                **{f"layer:{k}": np.asarray(v)
                   for k, v in out.per_layer.items()}}

    def kernels(self, seeds):
        H, N, S = self.hops(seeds), len(self.flows_json), len(seeds)
        L = self.topo.num_links
        return [Kernel("walk", dict(H=H, N=N, S=S, F=5,
                                    tables=self.table_bytes())),
                Kernel("counts_fn", dict(H=H, N=N, S=S, L=L)),
                Kernel("fim_fn", dict(S=S, L=L))]

    def reference(self, seeds, dtype=np.float64):
        agg, per_layer = ref.fim_sweep(
            self.topo, self.flows_json, seeds,
            demand=self.spec_fields.get("demand_mode", "uniform"),
            dtype=dtype)
        return {"aggregate": agg,
                **{f"layer:{k}": v for k, v in per_layer.items()}}

    def gaps(self, answers, want):
        if set(answers) != set(want):
            return {"fim_gap": float("inf")}
        return {"fim_gap": max(rel_gap(answers[k], want[k]) for k in want)}

    def columns(self, answers, idx):
        return {k: v[idx] for k, v in answers.items()}


FAMILIES = {
    "monte_carlo_throughput": Throughput,
    "monte_carlo_fim": Fim,
}


def family(config_dir: Path, traffic: dict) -> Family:
    return FAMILIES[traffic["front_end"]](config_dir, traffic)
