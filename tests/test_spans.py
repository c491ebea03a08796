"""Host spans and counters of the fused sweep (core/spans.py).

With the profiler off nothing is recorded.  Under ``jax.profiler.trace``
both fused front ends record every stage span, nested in the front
end's span on the calling thread's host line; the self times of a call
add up to its front-end span; each copy span counts the bytes its
arrays' shapes give; the fill counts its freeze rounds and the walk its hops; the table
starts afresh in the next profiler session; backend compiles are
counted in the span that triggered them; and ``compile_fabric`` counts
what it built.
"""

import glob

import jax
import numpy as np
import pytest

from repro.core import (
    SimSpec, bipartite_pairs, build_paper_testbed, build_three_tier_clos,
    compile_fabric, monte_carlo_fim, monte_carlo_throughput, nic_ip,
    resolve_flows, server_name, spans, synthesize_flows,
)
from repro.core import jax_engine

SEEDS = np.arange(64)
JAX = SimSpec(engine="jax")
FIELDS = 5                          # FIELDS_5TUPLE hash columns per flow
#: route-table bytes a flow of the testbed: hop t's (W_t, C_t) are
#: (1, 2), (1, 16), (4, 4), (1, 2); a uint32 salt and an int32 count per
#: device, an int32 step per slot, one int32 exit
TESTBED_ROUTE_BYTES = 4 * 7 + 4 * 7 + 4 * 36 + 4

STAGES = {
    "monte_carlo_throughput": [
        "prep", "walk.to_device", "walk.run", "walk.to_host",
        "fill.run", "fill.to_host", "assemble"],
    "monte_carlo_fim": [
        "prep", "walk.to_device", "walk.run", "walk.to_host",
        "counts.run", "fim.run", "fim.to_host", "assemble"],
}
FRONT = {"monte_carlo_throughput": monte_carlo_throughput,
         "monte_carlo_fim": monte_carlo_fim}


@pytest.fixture(scope="module")
def sweep():
    comp = compile_fabric(build_paper_testbed())
    wl = bipartite_pairs([server_name(i) for i in range(8)],
                         [server_name(8 + i) for i in range(8)],
                         flows_per_pair=2)
    flows = resolve_flows(comp, wl)
    for fn in FRONT.values():       # compile outside the traces
        fn(comp, flows, SEEDS, spec=JAX)
    return comp, flows


def traced(tmp_path, fn, *args, **kw):
    with jax.profiler.trace(str(tmp_path)):
        out = fn(*args, **kw)
    return out, spans.snapshot()


def test_profiler_off_records_nothing(sweep):
    comp, flows = sweep
    before = spans.snapshot()
    assert not spans.enabled()
    for fn in FRONT.values():
        fn(comp, flows, SEEDS, spec=JAX)
    with spans.span("outside") as s:
        spans.count("bytes", 1)
    assert s is None
    assert spans.snapshot() == before


@pytest.mark.parametrize("front", sorted(STAGES))
def test_traced_call_records_every_span_nested_on_calling_thread(
        sweep, tmp_path, front):
    comp, flows = sweep
    _, table = traced(tmp_path, FRONT[front], comp, flows, SEEDS,
                      spec=JAX)
    assert set(table) == {front, *STAGES[front]}
    assert all(row["n"] == 1 for row in table.values())
    assert table[front]["flows"] == len(flows)
    assert table[front]["seeds"] == len(SEEDS)

    from jax.profiler import ProfileData

    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = [ln for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for ln in p.lines
             if any(e.name in STAGES[front] for e in ln.events)]
    assert len(lines) == 1, "every stage span on the calling thread's line"
    events = {e.name: e for e in lines[0].events
              if e.name in table}
    assert set(events) == set(table)
    outer = events[front]
    for name in STAGES[front]:
        ev = events[name]
        assert outer.start_ns <= ev.start_ns
        assert ev.start_ns + ev.duration_ns <= (outer.start_ns
                                                + outer.duration_ns)


@pytest.mark.parametrize("front", sorted(STAGES))
def test_self_times_add_up_to_the_front_end_span(sweep, tmp_path, front):
    comp, flows = sweep
    _, table = traced(tmp_path, FRONT[front], comp, flows, SEEDS,
                      spec=JAX)
    assert sum(r["self_s"] for r in table.values()) == pytest.approx(
        table[front]["total_s"], rel=1e-9)
    for name in STAGES[front]:
        assert table[name]["self_s"] == table[name]["total_s"] > 0


def test_copy_spans_count_the_bytes_of_their_shapes(sweep, tmp_path):
    """Inputs in, answers out: the walk sends its fields, seeds and
    destinations and pulls its arrival check's one bool, the FIM its
    (1 + NL, S) answers and (NL,) live flags, and the walk's route
    tables and the FIM's layer tables go up once per compiled fabric,
    on its first call; the (S, L) count matrix never crosses."""
    comp, flows = sweep
    N, S, L = len(flows), len(SEEDS), comp.num_links
    walk = {"walk.to_device": N * 4 + N * FIELDS * 8 + S * 8,
            "walk.to_host": 1}
    _, table = traced(tmp_path / "tp", monte_carlo_throughput, comp, flows,
                      SEEDS, spec=JAX)
    got = {k: v["bytes"] for k, v in table.items() if "bytes" in v}
    assert got == {**walk, "fill.to_host": N * S * 8}

    NL = sum((comp.link_layer == i).any()
             for i in range(len(comp.layer_names)))
    fim_out = {"fim.to_host": (1 + NL) * S * 8 + NL}
    fresh = compile_fabric(build_paper_testbed())
    _, table = traced(tmp_path / "fim0", monte_carlo_fim, fresh, flows,
                      SEEDS, spec=JAX)
    got = {k: v["bytes"] for k, v in table.items() if "bytes" in v}
    assert got == {**walk, **fim_out, "fim.to_device": NL * L + 2 * L * 4,
                   "walk.tables": N * TESTBED_ROUTE_BYTES}
    _, table = traced(tmp_path / "fim1", monte_carlo_fim, fresh, flows,
                      SEEDS, spec=JAX)
    got = {k: v["bytes"] for k, v in table.items() if "bytes" in v}
    assert got == {**walk, **fim_out}


# one link shared by three flows freezes them all in one round; a flow
# held by link 0 (10 Gb/s, two flows) leaves link 1 (100 Gb/s) to its
# other flow, which freezes a round later
@pytest.mark.parametrize("ids, cap, rates, rounds", [
    ([[[0], [0], [0]]], [30.0], [10.0, 10.0, 10.0], 1),
    ([[[0], [0], [1]], [[-1], [1], [-1]]], [10.0, 100.0],
     [5.0, 5.0, 95.0], 2),
], ids=["one-link", "two-level"])
def test_fill_counts_its_rounds(ids, cap, rates, rounds):
    with jax.enable_x64(True):
        got, r = jax_engine._fill_device(
            np.asarray(ids, np.int32), np.asarray(cap), np.ones(3))
        np.testing.assert_array_equal(np.asarray(got)[:, 0], rates)
        assert int(r) == rounds


def test_traced_fill_counts_its_rounds(sweep, tmp_path):
    comp, flows = sweep
    _, table = traced(tmp_path, monte_carlo_throughput, comp, flows, SEEDS,
                      spec=JAX)
    assert table["fill.to_host"]["rounds"] >= 1
    assert "rounds" not in table["monte_carlo_throughput"]


def test_table_starts_afresh_in_the_next_session(sweep, tmp_path):
    comp, flows = sweep
    traced(tmp_path / "a", monte_carlo_throughput, comp, flows, SEEDS,
           spec=JAX)
    _, table = traced(tmp_path / "b", monte_carlo_fim, comp, flows, SEEDS,
                      spec=JAX)
    assert "monte_carlo_throughput" not in table
    assert "fill.run" not in table
    assert table["monte_carlo_fim"]["n"] == 1


def test_compiles_land_in_the_span_that_triggered_them(sweep, tmp_path):
    """A seed count no other test uses compiles the walk inside
    ``walk.run``; every backend compile of the session is counted once."""
    comp, flows = sweep
    seen = []

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        _, table = traced(tmp_path, monte_carlo_fim, comp, flows,
                          np.arange(37), spec=JAX)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert table["walk.run"]["compiles"] >= 1
    assert sum(r.get("compiles", 0) for r in table.values()) == len(seen)
    assert sum(r.get("compile_s", 0.0) for r in table.values()) == (
        pytest.approx(sum(seen)))


def _three_tier():
    """Two pods of one rack: each NIC of server i in pod 0 and the same
    NIC of server i in pod 1 exchange one flow each way (6 hops)."""
    fab = build_three_tier_clos(num_pods=2, racks_per_pod=1,
                                servers_per_rack=2, nics_per_server=2,
                                cluster_switches=2, aggs_per_plane=1,
                                uplinks=1)
    wl = bipartite_pairs([server_name(0), server_name(1)],
                         [server_name(2), server_name(3)], flows_per_pair=2)
    return fab, synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)


@pytest.mark.parametrize("shape, hops", [("testbed", 4), ("three-tier", 6)])
def test_walk_counts_its_hops_per_pass(sweep, tmp_path, monkeypatch, shape,
                                       hops):
    """``hops`` of ``walk.run`` is the walk loop's trip count, summed
    over the call's seed passes (two here)."""
    if shape == "testbed":
        comp, flows = sweep
    else:
        fab, flows = _three_tier()
        comp = compile_fabric(fab)
    per_seed = len(flows) * 16 * (jax_engine._WALK_BYTES_PER_HOP
                                  + jax_engine._FILL_BYTES_PER_CELL)
    monkeypatch.setattr(jax_engine, "_CHUNK_BYTES", per_seed * 128)
    seeds = np.arange(256)
    monte_carlo_fim(comp, flows, seeds, spec=JAX)
    _, table = traced(tmp_path, monte_carlo_fim, comp, flows, seeds,
                      spec=JAX)
    assert table["walk.run"]["n"] == 2
    assert table["walk.run"]["hops"] == 2 * hops
    # select_width: the route-table entries a (flow, seed) selects among
    ends = comp.flow_endpoint_ids(flows)
    widths = jax_engine._route_tables_host(comp, ends[0], ends[2], ends[3],
                                           16)[2]
    assert table["walk.run"]["select_width"] == 2 * sum(
        w * c for w, c in widths)
    if shape == "testbed":
        assert widths == ((1, 2), (1, 16), (4, 4), (1, 2))


def test_route_tables_are_built_once_per_fabric_and_flows(sweep, tmp_path):
    """The ``walk.tables`` span builds and uploads the walk's route
    tables on a compiled fabric's first call with these flows only; a
    replaced fabric, or other flows, builds them again."""
    import dataclasses

    comp, flows = sweep
    comp = dataclasses.replace(comp)
    _, first = traced(tmp_path / "a", monte_carlo_fim, comp, flows, SEEDS,
                      spec=JAX)
    _, again = traced(tmp_path / "b", monte_carlo_fim, comp, flows, SEEDS,
                      spec=JAX)
    _, other = traced(tmp_path / "c", monte_carlo_fim, comp, flows[:8],
                      SEEDS, spec=JAX)
    _, replaced = traced(tmp_path / "d", monte_carlo_fim,
                         dataclasses.replace(comp), flows, SEEDS,
                         spec=JAX)
    # 32 flows: 8 server pairs each way, on both NICs of a server
    assert first["walk.tables"] == {
        **first["walk.tables"], "n": 1, "triples": 32,
        "bytes": len(flows) * TESTBED_ROUTE_BYTES}
    assert "walk.tables" not in again
    assert other["walk.tables"]["n"] == 1
    assert replaced["walk.tables"]["n"] == 1
    for table in (first, again, other, replaced):
        assert table["walk.run"]["select_width"] == 36


def test_compile_fabric_span_counts_what_it_built(tmp_path):
    fab, _ = _three_tier()
    comp, table = traced(tmp_path, compile_fabric, fab)
    V, K, C = comp.cand.shape
    assert table["compile_fabric"]["n"] == 1
    assert {k: table["compile_fabric"][k] for k in (
        "devices", "links", "keys", "table_bytes")} == {
        "devices": V, "links": len(fab.links), "keys": K,
        "table_bytes": V * K * C * 4 + V * K * 4}
