"""The traffic generator: the same work for every seed, on other tokens."""
import json
import os

import numpy as np
import pytest

import smoke_cell
from chipbench import spec, traffic


def load(mix):
    """A mix of the benchmark, or one of the test data."""
    path = os.path.join(smoke_cell.DATA, "traffic", mix + ".json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return spec.load_traffic(mix)


@pytest.mark.parametrize("mix", ["batch-decode", "smoke-closed"])
def test_every_seed_offers_the_same_work(mix):
    m = load(mix)
    seen = []
    for seed in (1, 2**31 + 7, 987654321):
        t = traffic.Traffic(m, seed, vocab=49152)
        reqs = [t.next() for _ in range(3 * m["block"])]
        seen.append(reqs)
        assert all(1 <= int(p.min()) and int(p.max()) < 49152
                   for p, _ in reqs)
        assert t.max_len == m["prompt"]["max"] + m["output"]["max"]
    for i in range(3):
        for field in range(2):
            blocks = [sorted(len(x[0]) if field == 0 else x[field]
                             for x in r[16 * i:16 * (i + 1)]) for r in seen]
            assert all(b == blocks[0] for b in blocks[1:])
    # the sizes come in one order for every seed; the token ids do not
    sizes = [[(len(p), n) for p, n in r] for r in seen]
    assert sizes[0] == sizes[1] == sizes[2]
    assert not np.array_equal(seen[0][0][0], seen[1][0][0])
    assert traffic.spread_order(16)[:5].tolist() == [0, 8, 4, 12, 2]
    assert sorted(traffic.spread_order(13)) == list(range(13))


def test_quantiles_follow_the_clipped_pareto_law():
    law = {"law": "pareto", "alpha": 1.2, "scale": 16, "min": 16, "max": 256}
    q = traffic.quantiles(law, 16)
    assert q.min() >= 16 and q.max() == 256
    assert 24 <= np.median(q) <= 34          # median about 16 * 2 ** (1/1.2)
    with pytest.raises(ValueError):
        traffic.quantiles(dict(law, law="lognormal"), 16)


def test_residual_budgets_are_length_biased():
    outputs = np.array([10, 30])
    r = traffic.residual_quantiles(outputs, 400)
    # P(R = r) is 2/40 for r <= 10 and 1/40 above: a quarter of the mass
    # lies at or below 5, half at or below 10
    assert r.min() >= 1 and r.max() <= 30
    assert np.mean(r <= 10) == pytest.approx(0.5, abs=0.01)
    assert np.mean(r <= 5) == pytest.approx(0.25, abs=0.01)


@pytest.mark.parametrize("slots", [16, 13])
def test_closed_loop_fill_draws_residual_budgets(slots):
    m = spec.load_traffic("batch-decode")
    t = traffic.Traffic(m, 5, vocab=100)
    fill = t.fill(slots)
    assert len(fill) == slots
    assert all(1 <= n <= m["output"]["max"] for _, n in fill)
    assert min(n for _, n in fill) < m["output"]["min"]
    # the fill is the same for every seed, but for its token ids
    other = traffic.Traffic(m, 2**31 + 99, vocab=100).fill(slots)
    assert [(len(p), n) for p, n in fill] == [(len(p), n) for p, n in other]
