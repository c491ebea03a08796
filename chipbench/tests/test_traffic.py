"""The hash seeds a window's calls get: the same work for every run seed."""

from __future__ import annotations

import json

import numpy as np
import pytest

from chipbench import run as R

BENCH = json.loads((R.ROOT / "BENCHMARK.json").read_text())
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})


def _traffic(mix: str) -> dict:
    return R.load_json(R.HERE / "traffic" / f"{mix}.json")


def _calls(traffic: dict, seed: int, n: int) -> list[bytes]:
    return [R.call_seeds(traffic, seed, c).tobytes() for c in range(n)]


@pytest.mark.parametrize("mix", MIXES)
def test_every_run_seed_draws_the_same_calls_in_its_own_order(mix):
    traffic = dict(_traffic(mix), seeds_per_call=8)
    n = 3 * int(traffic["order_block"])          # whole blocks
    seeds = [2**31 + 5, 2**33 + 1, 7]
    runs = [_calls(traffic, s, n) for s in seeds]
    assert all(sorted(r) == sorted(runs[0]) for r in runs)
    assert len(set(runs[0])) == n                # no call repeats a set
    assert len({tuple(r) for r in runs}) > 1     # the seed orders them
    assert _calls(traffic, seeds[0], n) == runs[0]


def test_call_seeds_are_the_calls_hash_seeds():
    traffic = dict(_traffic(MIXES[0]), seeds_per_call=16)
    got = R.call_seeds(traffic, 2**40 + 3, 0)
    assert got.dtype == np.uint64 and got.shape == (16,)
