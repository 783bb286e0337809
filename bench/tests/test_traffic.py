"""The traffic generator gives every seed the same work: the same
(prompt, output) pairs in every burst, in another order, with other
token ids."""

import json
from pathlib import Path

import pytest

from bench.lib import traffic

ROOT = Path(__file__).resolve().parents[2]
MIXES = [ROOT / "bench" / "traffic" / "coldburst.json",
         ROOT / "bench" / "tests" / "data" / "tinyburst.json"]
SEEDS = (0, 1, 12345, 2 ** 31 + 11, 3_054_198_127)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_and_cycle_serves_the_same_pairs(path):
    mix = json.loads(path.read_text())
    fixed = traffic.pairs(mix)
    n = mix["burst"]
    assert sorted(p for p, _ in fixed) == \
        sorted(traffic.lengths(mix["prompt_len"], n))
    assert sorted(o for _, o in fixed) == \
        sorted(traffic.lengths(mix["output_len"], n))
    orders, ids = set(), set()
    for seed in SEEDS:
        for cycle in (0, 1, 2):
            reqs = traffic.burst(mix, 1000, seed, cycle)
            got = [(len(r.prompt), r.max_new) for r in reqs]
            assert sorted(got) == sorted(fixed)
            assert all(0 <= t < 1000 for r in reqs for t in r.prompt)
            orders.add(tuple(got))
            ids.add(tuple(reqs[0].prompt[:8]))
    assert len(orders) > 1 and len(ids) > 1


def test_same_seed_same_requests():
    mix = json.loads(MIXES[0].read_text())
    a = traffic.burst(mix, 49155, 2 ** 31 + 11, 1)
    b = traffic.burst(mix, 49155, 2 ** 31 + 11, 1)
    assert a == b
