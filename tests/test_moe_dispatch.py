"""DeepSeek-V3 expert-parallel dispatch (core/llm_workload.py): the
vectorized group-limited router against a per-token loop of the same
rule; the dispatch's invariants and its skew; its flows through the
fused byte-weighted FIM against the hop-by-hop tracer with a scalar
weighted FIM and against the benchmark's reference; the weighted counts'
counters; and the frozen benchmark cell built from it."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import (
    EcmpRouting, FlowTracer, MoeDispatchSpec, SimSpec, build_three_tier_clos,
    compile_fabric, moe_dispatch_flows, monte_carlo_fim, nic_ip, spans,
    workload_from_flows,
)
from repro.core.llm_workload import (
    QP_BASE_PORT, dispatch_counts, expert_popularity, route_tokens,
    router_logits, source_domains,
)

ROOT = Path(__file__).resolve().parents[1]
CELL = ROOT / "chipbench" / "configs" / "deepseek-v3-ep64-meta-pod"

#: 2 EP groups x 4 nodes x 2 GPUs, 64 tokens a GPU; 32 experts in 4
#: groups of 8, top 4 experts of the top 2 groups
SMALL = dict(ep_groups=2, n_group=4, gpus_per_node=2, tokens_per_gpu=64,
             n_routed_experts=32, num_experts_per_tok=4, topk_group=2)
#: one pod of 4 racks x 2 servers x 2 single-port NICs: the 8 servers of
#: SMALL's two EP groups
POD = dict(num_pods=1, racks_per_pod=4, servers_per_rack=2,
           nics_per_server=2, cluster_switches=4, aggs_per_plane=2,
           uplinks=2, link_gbps=400.0)


def _load_reference():
    path = ROOT / "chipbench" / "reference.py"
    spec = importlib.util.spec_from_file_location("flowtracer_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def loop_route(row, spec):
    """One token's experts and kept groups by the rule written out."""
    s = [1.0 / (1.0 + math.exp(-x)) for x in row]
    per = spec.n_routed_experts // spec.n_group
    group_score = [sum(sorted(s[i * per:(i + 1) * per])[-2:])
                   for i in range(spec.n_group)]
    groups = sorted(range(spec.n_group),
                    key=lambda i: -group_score[i])[:spec.topk_group]
    cands = [e for i in groups for e in range(i * per, (i + 1) * per)]
    experts = sorted(cands, key=lambda e: -s[e])[:spec.num_experts_per_tok]
    return sorted(experts), sorted(groups)


def cv(x) -> float:
    x = np.asarray(x, np.float64)
    return float(x.std() / x.mean())


@pytest.mark.parametrize("skew", [0.0, 0.99])
@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_router_matches_a_per_token_loop(seed, skew):
    spec = MoeDispatchSpec(**SMALL, zipf_skew=skew, seed=seed)
    per = spec.n_routed_experts // spec.n_group
    want = np.zeros((spec.ep_groups, spec.n_group, spec.gpus_per_node,
                     spec.n_group), np.int64)
    for e in range(spec.ep_groups):
        for a in range(spec.n_group):
            logits = router_logits(spec, e, a)
            experts, groups = route_tokens(
                logits, n_group=spec.n_group, topk_group=spec.topk_group,
                top_k=spec.num_experts_per_tok)
            for g in range(spec.gpus_per_node):
                for t in range(spec.tokens_per_gpu):
                    ex, gr = loop_route(logits[g, t], spec)
                    assert experts[g, t].tolist() == ex
                    assert groups[g, t].tolist() == gr
                    for b in {x // per for x in ex} - {a}:
                        want[e, a, g, b] += 1
    np.testing.assert_array_equal(dispatch_counts(spec), want)


def test_dispatch_invariants():
    """Experts come from the kept groups, at most ``topk_group`` nodes a
    token, no node dispatches to itself, and every flow runs from GPU g's
    own NIC to NIC g of another node of its EP group."""
    spec = MoeDispatchSpec(ep_groups=2, gpus_per_node=2, tokens_per_gpu=256,
                           seed=11)
    per = spec.n_routed_experts // spec.n_group
    experts, groups = route_tokens(
        router_logits(spec, 1, 3), n_group=spec.n_group,
        topk_group=spec.topk_group, top_k=spec.num_experts_per_tok)
    assert experts.shape == (2, 256, 8) and groups.shape == (2, 256, 4)
    assert (np.diff(experts, axis=-1) > 0).all()
    nodes = experts // per
    assert (nodes[..., :, None] == groups[..., None, :]).any(-1).all()
    reached = [len(set(row)) for row in nodes.reshape(-1, 8).tolist()]
    assert max(reached) <= spec.topk_group

    counts = dispatch_counts(spec)
    for a in range(spec.n_group):
        assert (counts[:, a, :, a] == 0).all()
    assert (counts.sum(-1) <= 256 * spec.topk_group).all()
    flows = moe_dispatch_flows(spec, counts)
    assert len(flows) == np.count_nonzero(counts) * spec.qps
    assert [f.flow_id for f in flows] == list(range(len(flows)))
    for f in flows:
        src, dst = int(f.src.split("-")[1]), int(f.dst.split("-")[1])
        assert src != dst and src // 8 == dst // 8
        g = int(f.tuple5.src_ip.split(".")[1])
        assert f.tuple5.src_ip == nic_ip(f.src, g)
        assert f.tuple5.dst_ip == nic_ip(f.dst, g)
        q = f.tuple5.src_port - QP_BASE_PORT
        assert 0 <= q < spec.qps and f.tuple5.dst_port == 4791
        c = counts[src // 8, src % 8, g, dst % 8]
        assert f.bytes == c * 7392 // 16 and f.label == "moe-dispatch#ch4"


@pytest.mark.parametrize("skew, low, high", [
    (0.0, 0.0, 0.1),                    # every expert as popular
    (0.99, 0.3, 2.0),                   # Zipf(0.99) per data domain
], ids=["no-skew", "zipf-0.99"])
def test_skew_spreads_the_counts(skew, low, high):
    """Per-(GPU, node) token counts spread little without popularity
    skew and by more with one Zipf domain a GPU; the same seed gives
    the same counts, another seed others."""
    spec = MoeDispatchSpec(tokens_per_gpu=1024, zipf_skew=skew, seed=5)
    counts = dispatch_counts(spec)
    sent = counts[counts > 0]
    assert sent.size == 8 * 8 * 7              # no pair is empty
    assert low <= cv(sent) < high
    np.testing.assert_array_equal(dispatch_counts(spec), counts)
    other = dispatch_counts(dataclasses.replace(spec, seed=6))
    assert not np.array_equal(other, counts)


def test_popularity_and_domains_are_drawn_from_the_seed():
    spec = MoeDispatchSpec(**SMALL, seed=3)
    p = expert_popularity(spec)
    assert p.shape == (8, 32)
    np.testing.assert_allclose(p.sum(1), 1.0, rtol=1e-12)
    zipf = np.arange(1, 33) ** -0.99
    np.testing.assert_allclose(np.sort(p, axis=1)[:, ::-1],
                               np.tile(zipf / zipf.sum(), (8, 1)),
                               rtol=1e-12)
    assert len({tuple(np.argsort(row)) for row in p}) == 8
    d = source_domains(spec)
    assert d.shape == (2, 4, 2) and d.min() >= 0 and d.max() < 8
    np.testing.assert_array_equal(source_domains(spec), d)


def test_zero_count_pair_emits_no_flow():
    spec = MoeDispatchSpec(**SMALL, qps=4)
    counts = np.zeros((2, 4, 2, 4), np.int64)
    counts[0, 0, 1, 2] = 3
    counts[1, 3, 0, 1] = 5
    flows = moe_dispatch_flows(spec, counts)
    assert [(f.src, f.dst, f.tuple5.src_ip, f.bytes) for f in flows] == (
        [("srv-0", "srv-2", "10.1.0.0", 3 * 7392 // 4)] * 4
        + [("srv-7", "srv-5", "10.0.0.7", 5 * 7392 // 4)] * 4)
    assert [f.tuple5.src_port for f in flows] == [49152, 49153, 49154,
                                                 49155] * 2
    counts[0, 0, 1, 0] = 1                       # node 0 to itself
    with pytest.raises(ValueError, match="itself"):
        moe_dispatch_flows(spec, counts)


def scalar_weighted_fim(paths, fab, weights):
    """Per layer: link loads as sums of the weights of the flows that
    cross each link; MAPE against the layer's mean; the aggregate
    weighted by the layer's links.  Layers that carry nothing drop."""
    load: dict[str, float] = {}
    for fid, path in paths.items():
        for link in path:
            load[link.name] = load.get(link.name, 0.0) + weights[fid]
    per_layer = {}
    for layer in fab.layers:
        links = fab.links_by_layer(layer)
        c = [load.get(ln.name, 0.0) for ln in links]
        if not links or sum(c) == 0:
            continue
        ideal = sum(c) / len(links)
        per_layer[layer] = (100.0 / len(links) * sum(
            abs(x - ideal) / ideal for x in c), len(links))
    agg = (sum(v * n for v, n in per_layer.values())
           / sum(n for _, n in per_layer.values()))
    return agg, {k: v for k, (v, _) in per_layer.items()}


@pytest.mark.parametrize("seed", [0, 1])
def test_dispatch_fim_matches_tracer_and_reference(seed, tmp_path):
    """The dispatch on a 4-rack pod through the fused byte-weighted FIM:
    equal to the tracer's paths with a scalar weighted FIM under the
    exact hash, and to the benchmark's reference under murmur, at
    1e-12.  The weighted counts count themselves on ``counts.run``; the
    same flows at uniform demand take the unit path and do not."""
    fab = build_three_tier_clos(**POD)
    comp = compile_fabric(fab)
    spec = MoeDispatchSpec(**SMALL, seed=seed)
    flows = moe_dispatch_flows(spec)
    sizes = np.array([f.bytes for f in flows], np.float64)
    assert cv(sizes) > 0.1
    weights = sizes / sizes.mean()
    seeds = np.random.default_rng(seed).integers(
        0, 2**62, 6, dtype=np.int64).astype(np.uint64)
    bytes_jax = SimSpec(engine="jax", demand_mode="bytes")

    exact = monte_carlo_fim(
        comp, flows, seeds,
        spec=dataclasses.replace(bytes_jax, hash_backend="exact"))
    wl = workload_from_flows(flows)
    for i, s in enumerate(seeds):
        paths = FlowTracer(fab, EcmpRouting(fab, seed=int(s)), wl,
                           flows).trace().paths
        agg, per_layer = scalar_weighted_fim(paths, fab, weights)
        assert exact.aggregate[i] == pytest.approx(agg, rel=1e-12)
        assert sorted(exact.per_layer) == sorted(per_layer)
        for layer, value in per_layer.items():
            assert exact.per_layer[layer][i] == pytest.approx(value,
                                                              rel=1e-12)

    ref = _load_reference()
    rows = [{"src": f.src, "dst": f.dst, "src_ip": f.tuple5.src_ip,
             "dst_ip": f.tuple5.dst_ip, "src_port": f.tuple5.src_port,
             "dst_port": f.tuple5.dst_port, "protocol": f.tuple5.protocol,
             "bytes": f.bytes} for f in flows]
    agg, per_layer = ref.fim_sweep(ref.Topology(fab.to_json()), rows, seeds,
                                   demand="bytes")
    with jax.profiler.trace(str(tmp_path / "bytes")):
        mc = monte_carlo_fim(comp, flows, seeds,
                             spec=dataclasses.replace(bytes_jax,
                                                      hash_backend="murmur"))
    table = spans.snapshot()
    np.testing.assert_allclose(mc.aggregate, agg, rtol=1e-12)
    assert set(mc.per_layer) == set(per_layer)
    for layer, want in per_layer.items():
        np.testing.assert_allclose(mc.per_layer[layer], want, rtol=1e-12)

    row, hops = table["counts.run"], table["walk.run"]["hops"]
    assert row["weighted_counts"] == row["n"] == 1
    assert row["link_compares"] == hops * len(flows) * 6 * comp.num_links
    assert "unit_counts" not in row
    with jax.profiler.trace(str(tmp_path / "uniform")):
        monte_carlo_fim(comp, flows, seeds, spec=SimSpec(engine="jax"))
    row = spans.snapshot()["counts.run"]
    assert row["unit_counts"] == 1
    assert "weighted_counts" not in row and "link_compares" not in row


def test_dispatch_span_counts_tokens_pairs_and_flows(tmp_path):
    spec = MoeDispatchSpec(**SMALL, seed=4)
    with jax.profiler.trace(str(tmp_path)):
        flows = moe_dispatch_flows(spec)
    row = spans.snapshot()["moe.dispatch"]
    assert row["n"] == 1 and row["tokens"] == 2 * 4 * 2 * 64
    assert row["pairs"] == np.count_nonzero(dispatch_counts(spec))
    assert row["flows"] == len(flows) == row["pairs"] * 16


def test_frozen_dispatch_cell():
    """The benchmark's frozen DeepSeek-V3 dispatch: one pod of 48 racks
    (192 devices, 3264 links), 12 EP groups of 8 servers, 16 flows for
    each of the 5376 (GPU, node) pairs, none empty, with bytes a
    multiple of 7392 / 16 that spread by a CV of at least 0.3."""
    fabric = json.loads((CELL / "fabric.json").read_text())
    assert (len(fabric["devices"]), len(fabric["links"])) == (192, 3264)
    flows = json.loads((CELL / "flows.json").read_text())["flows"]
    assert len(flows) == 12 * 8 * 8 * 7 * 16
    pairs: dict[tuple, list[int]] = {}
    for f in flows:
        src, dst = int(f["src"].split("-")[1]), int(f["dst"].split("-")[1])
        assert src != dst and src // 8 == dst // 8
        assert f["src_ip"].split(".")[1] == f["dst_ip"].split(".")[1]
        pairs.setdefault((f["src_ip"], f["dst"]), []).append(f["bytes"])
    assert len(pairs) == 5376
    assert all(len(set(b)) == 1 and len(b) == 16 for b in pairs.values())
    per_pair = np.array([b[0] for b in pairs.values()])
    assert (per_pair % 462 == 0).all() and per_pair.min() > 0
    assert cv(per_pair) >= 0.3
