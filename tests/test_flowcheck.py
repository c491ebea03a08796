"""flowcheck analyzer + runtime contract mode (src/repro/analysis).

Fixture-driven: each rule family must trip on a known-bad snippet and
stay silent on the repo's own known-good idioms (static-shape loops,
``static_argnames`` branches, ``is not None`` structure dispatch, the
per-call-site taint that keeps ``hash_backend`` comparisons clean).
The baseline must round-trip (write -> justify -> clean), reject TODO
justifications, and still fail on findings it has never seen.  And the
real repo must be clean against its committed baseline — the same
gate CI runs.
"""

import json
import textwrap

import numpy as np
import pytest

from repro.analysis.common import Context
from repro.analysis.flowcheck import collect_findings, main

# ---------------------------------------------------------------------------
# fixture repos
# ---------------------------------------------------------------------------


def make_repo(tmp_path, files):
    """A throwaway repo tree: {relative path: dedented source}."""
    (tmp_path / "src" / "repro").mkdir(parents=True, exist_ok=True)
    (tmp_path / "tests").mkdir(exist_ok=True)
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return tmp_path


def rules_of(root):
    return [f.rule for f in collect_findings(Context(root=root))]


# ---------------------------------------------------------------------------
# FT-JIT: retrace / host-sync hazards
# ---------------------------------------------------------------------------


def test_jit_family_trips_each_rule_once(tmp_path):
    root = make_repo(tmp_path, {"src/repro/core/jax_engine.py": """\
        import functools
        import jax
        import numpy as np


        @functools.partial(jax.jit, static_argnames=("mode",))
        def bad(x, y, mode):
            if x > 0:                    # FT-JIT-BRANCH
                y = y + 1
            for v in x:                  # FT-JIT-LOOP
                y = y + v
            z = float(x[0])              # FT-JIT-HOSTSYNC
            w = np.sum(x)                # FT-JIT-NUMPY
            return y + z + w
        """})
    rules = rules_of(root)
    for rule in ("FT-JIT-BRANCH", "FT-JIT-LOOP", "FT-JIT-HOSTSYNC",
                 "FT-JIT-NUMPY"):
        assert rules.count(rule) == 1, (rule, rules)


def test_jit_known_good_idioms_stay_clean(tmp_path):
    # the repo's own jit vocabulary: static_argnames branches,
    # static-shape loops, None structure dispatch, and a helper whose
    # *string* argument is compared while its array argument is traced
    root = make_repo(tmp_path, {"src/repro/core/jax_engine.py": """\
        import functools
        import jax
        import jax.numpy as jnp

        EXACT = "exact"


        def _hash_grid(fields, dev_seed, backend):
            if backend == EXACT:         # static at every call site
                return fields + dev_seed
            return fields * dev_seed


        @functools.partial(jax.jit, static_argnames=("cool", "near"))
        def walk(fields, dev_seed, cell_salt, cool, near):
            acc = jnp.zeros(fields.shape[0], dtype=jnp.float64)
            if cool and near:            # static_argnames
                acc = acc + 1
            if cell_salt is not None:    # structure dispatch
                acc = acc + cell_salt
            for f in range(fields.shape[1]):   # static shape
                acc = acc + _hash_grid(fields[:, f], dev_seed, EXACT)
            n = len(fields)              # static: len of traced array
            return acc / n
        """})
    assert [r for r in rules_of(root) if r.startswith("FT-JIT")] == []


def test_jit_taint_reaches_same_module_helpers(tmp_path):
    root = make_repo(tmp_path, {"src/repro/core/strategies.py": """\
        import jax


        def _helper(a):
            if a.sum() > 0:              # traced via the call below
                return a * 2
            return a


        @jax.jit
        def entry(arr):
            return _helper(arr)
        """})
    assert rules_of(root).count("FT-JIT-BRANCH") == 1


def test_jit_span_flags_spans_inside_traced_code_only(tmp_path):
    # a span or counter inside jit records once, at trace time; around
    # the jitted call on the host it is the intended use
    root = make_repo(tmp_path, {"src/repro/core/jax_engine.py": """\
        import jax
        from jax import lax

        from .spans import count, span


        @jax.jit
        def fill(x):
            with span("fill.inner"):         # FT-JIT-SPAN
                def body(c):
                    count("rounds", 1)       # FT-JIT-SPAN
                    return c - 1
                return lax.while_loop(lambda c: c > 0, body, x)


        def host(x):
            with span("fill.run"):
                out = fill(x)
            count("rounds", 1)
            return out
        """})
    assert rules_of(root).count("FT-JIT-SPAN") == 2


# ---------------------------------------------------------------------------
# FT-DT: dtype drift
# ---------------------------------------------------------------------------


def test_dtype_family_trips_each_rule_once(tmp_path):
    root = make_repo(tmp_path, {"src/repro/core/jax_engine.py": """\
        import jax.numpy as jnp
        import numpy as np


        def build(n):
            a = np.arange(n)             # FT-DT-ARANGE
            b = np.array([1, 2, 3])      # FT-DT-LITERAL
            c = jnp.zeros(n)             # FT-DT-JNP
            return a, b, c
        """})
    rules = rules_of(root)
    for rule in ("FT-DT-ARANGE", "FT-DT-LITERAL", "FT-DT-JNP"):
        assert rules.count(rule) == 1, (rule, rules)


def test_dtype_pinned_calls_stay_clean(tmp_path):
    root = make_repo(tmp_path, {"src/repro/core/vector_sim.py": """\
        import numpy as np


        def build(n, loads, seg):
            a = np.arange(n, dtype=np.int64)
            b = np.array([1, 2, 3], dtype=np.uint64)
            c = np.zeros(n, bool)            # positional dtype
            d = np.asarray(loads)            # array passthrough: no flag
            e = np.add.reduceat(loads, seg)  # fast path untouched
            return a, b, c, d, e
        """})
    assert [r for r in rules_of(root) if r.startswith("FT-DT")] == []


# ---------------------------------------------------------------------------
# FT-REG: registry hygiene
# ---------------------------------------------------------------------------


def test_registry_family_trips_each_rule_once(tmp_path):
    root = make_repo(tmp_path, {
        "src/repro/core/strategies.py": """\
            def register_strategy(name, cls=None):
                pass


            def _lazy():
                register_strategy("inside")       # FT-REG-TOPLEVEL


            register_strategy("ecmp")
            register_strategy("ecmp")             # FT-REG-DUP
            register_strategy("orphan")           # FT-REG-UNTESTED

            import os
            register_strategy(os.environ["X"])    # FT-REG-OPAQUE
            """,
        "tests/test_strategies.py": """\
            def test_names():
                assert "ecmp" and "inside"
            """,
    })
    rules = rules_of(root)
    for rule in ("FT-REG-TOPLEVEL", "FT-REG-DUP", "FT-REG-UNTESTED",
                 "FT-REG-OPAQUE"):
        assert rules.count(rule) == 1, (rule, rules)


def test_registry_loop_and_ctor_names_resolve(tmp_path):
    # the reordering.py idiom: profiles registered from a module-level
    # for-loop over constructor-built constants
    root = make_repo(tmp_path, {
        "src/repro/core/reordering.py": """\
            class TransportProfile:
                def __init__(self, name, alpha=0.0):
                    self.name = name


            def register_transport(profile):
                pass


            IDEAL = TransportProfile(name="ideal")
            ROCE = TransportProfile("roce-nack", alpha=2.0)
            for _p in (IDEAL, ROCE):
                register_transport(_p)
            """,
        "tests/test_reordering.py": """\
            def test_profiles():
                assert "ideal" and "roce-nack"
            """,
    })
    assert [r for r in rules_of(root) if r.startswith("FT-REG")] == []


# ---------------------------------------------------------------------------
# FT-BENCH: bench rows vs the smoke baseline
# ---------------------------------------------------------------------------

_BENCH_BASELINE = json.dumps({"rows": [
    {"name": "walk_ecmp_64f", "us_per_call": 10.0},
    {"name": "hetero_tail_fim_pct", "us_per_call": 1.0},
]})


def test_bench_family_flags_uncovered_row(tmp_path):
    root = make_repo(tmp_path, {
        "benchmarks/BENCH_baseline_smoke.json": _BENCH_BASELINE,
        "benchmarks/walkbench.py": """\
            from common import emit


            def main():
                emit("walk_ecmp_64f", 1.0, {})
                emit("walk_new_row", 1.0, {})
            """,
    })
    assert rules_of(root).count("FT-BENCH-ROW") == 1


def test_bench_fstring_rows_and_pragma(tmp_path):
    root = make_repo(tmp_path, {
        "benchmarks/BENCH_baseline_smoke.json": _BENCH_BASELINE,
        "benchmarks/heterobench.py": """\
            from common import emit


            def main(scen):
                emit(f"hetero_{scen}_fim_pct", 1.0, {})
                emit("hetero_fresh", 1.0, {})  # flowcheck: new-bench-row
            """,
    })
    assert [r for r in rules_of(root) if r.startswith("FT-BENCH")] == []


def test_bench_uncovered_module_skipped(tmp_path):
    # a module with zero baseline presence is outside the smoke set
    root = make_repo(tmp_path, {
        "benchmarks/BENCH_baseline_smoke.json": _BENCH_BASELINE,
        "benchmarks/fig4.py": """\
            from common import emit


            def main():
                emit("fig4_everything", 1.0, {})
            """,
    })
    assert [r for r in rules_of(root) if r.startswith("FT-BENCH")] == []


# ---------------------------------------------------------------------------
# pragmas, baseline round-trip, CLI exit codes
# ---------------------------------------------------------------------------

_ONE_FINDING = {"src/repro/core/strategies.py": """\
    import numpy as np


    def build(n):
        return np.arange(n)
    """}


def test_line_pragma_suppresses(tmp_path):
    root = make_repo(tmp_path, {"src/repro/core/strategies.py": """\
        import numpy as np


        def build(n):
            return np.arange(n)  # flowcheck: disable=FT-DT-ARANGE
        """})
    assert rules_of(root) == []


def test_cli_baseline_round_trip(tmp_path, capsys):
    root = make_repo(tmp_path, _ONE_FINDING)
    base = root / "flowcheck_baseline.json"

    # no baseline: the finding is new -> exit 1
    assert main(["--root", str(root)]) == 1
    assert "FT-DT-ARANGE" in capsys.readouterr().out

    # write-baseline seeds TODO justifications -> check refuses (exit 2)
    assert main(["--root", str(root), "--write-baseline"]) == 0
    assert main(["--root", str(root)]) == 2
    assert "BROKEN BASELINE" in capsys.readouterr().out

    # justify -> clean (exit 0)
    payload = json.loads(base.read_text())
    for e in payload["entries"]:
        e["justification"] = "pre-existing; tracked in ISSUE backlog"
    base.write_text(json.dumps(payload))
    assert main(["--root", str(root)]) == 0

    # a NEW finding still fails against the old baseline
    (root / "src/repro/core/vector_sim.py").write_text(
        "import numpy as np\n\n\ndef f(n):\n    return np.arange(n)\n")
    assert main(["--root", str(root)]) == 1
    out = capsys.readouterr().out
    assert "vector_sim.py" in out and "1 new finding" in out


def test_cli_stale_baseline_is_advisory(tmp_path, capsys):
    root = make_repo(tmp_path, {"src/repro/core/empty.py": "X = 1\n"})
    (root / "flowcheck_baseline.json").write_text(json.dumps({
        "entries": [{"fingerprint": "FT-DT-ARANGE::gone.py::gone",
                     "justification": "was fixed"}]}))
    assert main(["--root", str(root)]) == 0
    assert "STALE" in capsys.readouterr().out


def test_cli_json_artifact(tmp_path):
    root = make_repo(tmp_path, _ONE_FINDING)
    out = tmp_path / "findings.json"
    assert main(["--root", str(root), "--json", str(out)]) == 1
    payload = json.loads(out.read_text())
    assert payload["new"] and payload["new"][0]["rule"] == "FT-DT-ARANGE"
    assert "FT-JIT-BRANCH" in payload["rules"]


def test_cli_rejects_non_repo_root(tmp_path):
    assert main(["--root", str(tmp_path / "nowhere")]) == 2


def test_real_repo_clean_against_committed_baseline():
    # the gate CI runs: the live tree must carry zero new findings
    assert main([]) == 0


# ---------------------------------------------------------------------------
# runtime contract mode (FLOWTRACER_CONTRACTS=1)
# ---------------------------------------------------------------------------


@pytest.fixture
def contracts_on(monkeypatch):
    monkeypatch.setenv("FLOWTRACER_CONTRACTS", "1")


def _routed(strategy=None):
    from repro.core import (
        SimSpec, bipartite_pairs, build_paper_testbed, compile_fabric, nic_ip,
        server_name, simulate_paths, synthesize_flows,
    )
    comp = compile_fabric(build_paper_testbed())
    wl = bipartite_pairs([server_name(i) for i in range(4)],
                         [server_name(8 + i) for i in range(4)],
                         flows_per_pair=2)
    flows = synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)
    return simulate_paths(comp, flows, [0, 1],
                          spec=SimSpec(strategy=strategy))


def test_contracts_off_by_default(monkeypatch):
    from repro.core import contracts_enabled
    monkeypatch.delenv("FLOWTRACER_CONTRACTS", raising=False)
    assert not contracts_enabled()
    for off in ("0", "false", "off", ""):
        monkeypatch.setenv("FLOWTRACER_CONTRACTS", off)
        assert not contracts_enabled()


def test_contracts_pass_on_healthy_pipeline(contracts_on):
    from repro.core import contracts_enabled, throughput_from_result
    assert contracts_enabled()
    res = _routed(strategy="prime-spray")
    tp = throughput_from_result(res, transport="roce-nack")
    assert np.isfinite(tp.goodput).all()


def test_contract_catches_bad_trace_result(contracts_on):
    from repro.core import ContractViolation
    from repro.core.contracts import check_trace_result
    res = _routed()
    res.demand = res.demand * 2.0          # flowlet fractions must sum to 1
    with pytest.raises(ContractViolation, match="sum to 1"):
        check_trace_result(res)
    res = _routed()
    res.link_ids = res.link_ids + res.compiled.num_links   # out of range
    with pytest.raises(ContractViolation, match="link ids"):
        check_trace_result(res)


def test_contract_catches_bad_throughput(contracts_on):
    from repro.core import ContractViolation, throughput_from_result
    from repro.core.contracts import check_throughput
    tp = throughput_from_result(_routed(strategy="prime-spray"),
                                transport="roce-nack")
    tp.goodput = tp.goodput * 2.0          # goodput must be rates x eff
    with pytest.raises(ContractViolation, match="goodput"):
        check_throughput(tp)


def test_contract_checks_resolved_spec(contracts_on):
    from repro.core import ContractViolation, SimSpec
    from repro.core.contracts import check_spec
    import dataclasses
    s = SimSpec(strategy="prime-spray").resolve()
    check_spec(s)                          # healthy resolve passes
    broken = dataclasses.replace(s, strategy="prime-spray")
    with pytest.raises(ContractViolation, match="name string"):
        check_spec(broken)
