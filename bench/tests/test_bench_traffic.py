"""The traffic generator: deterministic for a seed, and every seed the same
work in another order."""
from collections import Counter

import pytest

import tinybench  # noqa: F401  (puts the repository on the path)
from bench import spec as S

GEN = S.load_module(S.BENCH_DIR / "generators" / "lengths_arrivals.py")
MIXES = sorted(p.stem for p in (S.BENCH_DIR / "traffic").glob("*.json"))


def mix(name):
    return S.load_json(S.BENCH_DIR / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = GEN.block(mix(name), 40, 2**33 + 17, 0, 64000)
    b = GEN.block(mix(name), 40, 2**33 + 17, 0, 64000)
    assert a == b


@pytest.mark.parametrize("name", MIXES)
def test_seeds_permute_one_multiset(name):
    m = mix(name)
    a = GEN.block(m, 40, 1, 0, 64000)
    b = GEN.block(m, 40, 2, 0, 64000)
    lens = lambda reqs: Counter((len(r["prompt"]), ) for r in reqs)
    outs = lambda reqs: Counter(r["max_tokens"] for r in reqs)
    assert lens(a) == lens(b) and outs(a) == outs(b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert len(a) == GEN.window_requests(m, 40)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range_and_fit_the_slot(name):
    m = mix(name)
    reqs = GEN.block(m, 40, 5, 0, 64000)
    for r in reqs:
        assert m["prompt"]["min"] <= len(r["prompt"]) <= m["prompt"]["max"]
        assert m["output"]["min"] <= r["max_tokens"] <= m["output"]["max"]
        assert len(r["prompt"]) + r["max_tokens"] <= 1023   # max_seq - 1
        assert all(1 <= t < 64000 for t in r["prompt"])


def test_poisson_block_fills_its_window():
    m = mix("code")
    reqs = GEN.block(m, 40, 9, 3, 64000)
    due = sorted(r["due"] for r in reqs)
    assert due[0] == 0.0 and due[-1] < 40
    gaps = [b - a for a, b in zip(due, due[1:])]
    # the same stratified gaps in every seed: their mean is the rate's
    assert abs(sum(gaps) / len(gaps) - 1 / m["arrivals"]["rate_per_s"]) \
        < 0.1 / m["arrivals"]["rate_per_s"]


def test_backlog_is_due_at_once_and_once_only():
    m = mix("batch")
    assert all(r["due"] == 0.0 for r in GEN.block(m, 40, 1, 0, 64000))
    assert GEN.block(m, 40, 1, 1, 64000) == []


def test_quantiles_follow_the_lognormal_mean():
    q = GEN.lognormal_quantiles(2000, 400, 0.6, 1, 10**6)
    assert abs(q.mean() - 400) < 8
    assert list(q) == sorted(q)
