"""Traffic generators: the same seed gives the same requests; every seed
gives the same set of shapes in another order; lengths follow the mix."""
import numpy as np
import pytest

from bench import spec
from bench.traffic import _dists

MIX = {"generator": "open_loop", "rate_rps": 4.0, "pool": 512,
       "shapes_seed": 3,
       "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                      "min": 16, "max": 768},
       "output_len": {"dist": "uniform", "min": 16, "max": 64},
       "temperature": 0.7, "greedy_share": 0.5}


def _sends(name, seed, until=30.0, completions=()):
    gen = spec.generator(name)(dict(MIX, clients=4), seed, 1000)
    gen.start(0.0)
    out = gen.due(until)
    for rid in completions:
        gen.completed(rid, until)
    return out + gen.due(until)


def test_same_seed_same_requests():
    for name in ("open_loop", "closed_loop"):
        a, b = _sends(name, 2**31 + 5), _sends(name, 2**31 + 5)
        assert [(s.rid, s.due, s.max_new, s.temperature, s.seed)
                for s in a] == [(s.rid, s.due, s.max_new, s.temperature,
                                 s.seed) for s in b]
        assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
        c = _sends(name, 2**31 + 6)
        assert any(len(x.prompt) != len(y.prompt)
                   or (x.prompt != y.prompt).any() for x, y in zip(a, c))


def test_every_seed_the_same_shapes_in_another_order():
    a = _dists.shape_pool(MIX, 1)
    b = _dists.shape_pool(MIX, 2**31 + 2)
    for col in ("prompt_len", "max_new", "temperature", "gap_s"):
        x, y = ([getattr(s, col) for s in p] for p in (a, b))
        assert sorted(x) == sorted(y)
        assert x != y


def test_every_block_holds_every_stratum():
    mix = dict(MIX, stratify=8)
    base = np.array([s.prompt_len for s in _dists.shape_pool(MIX, 0)])
    edges = np.sort(base)[::len(base) // 8][1:]
    totals = []
    for seed in (1, 2, 2**31 + 3):
        pool = _dists.shape_pool(mix, seed)
        p = np.array([s.prompt_len for s in pool]).reshape(-1, 8)
        ranks = np.sort(np.searchsorted(edges, p, side="right"), axis=1)
        # a stratum boundary may fall inside a run of equal lengths
        assert (np.abs(ranks - np.arange(8)) <= 1).all()
        greedy = np.array([s.temperature == 0 for s in pool]).reshape(-1, 8)
        assert np.ptp(greedy.sum(axis=1)) <= 1
        totals.append(sum(s.max_new for s in pool[:24]))
    assert np.ptp(totals) <= 0.05 * np.mean(totals)


def test_stratify_needs_whole_blocks():
    with pytest.raises(ValueError, match="whole number of blocks"):
        _dists.shape_pool(dict(MIX, stratify=7), 1)


def test_length_distributions():
    pool = _dists.shape_pool(MIX, 9)
    p = np.array([s.prompt_len for s in pool])
    o = np.array([s.max_new for s in pool])
    assert p.min() >= 16 and p.max() <= 768
    assert 200 <= np.median(p) <= 310
    assert o.min() >= 16 and o.max() <= 64
    greedy = np.mean([s.temperature == 0 for s in pool])
    assert 0.4 < greedy < 0.6
    gaps = np.array([s.gap_s for s in pool])
    assert abs(gaps.mean() - 0.25) < 0.03        # 4 req/s


def test_open_loop_is_on_a_schedule_and_closed_loop_waits():
    sends = _sends("open_loop", 1, until=10.0)
    dues = [s.due for s in sends]
    assert dues == sorted(dues) and dues[-1] <= 10.0
    assert 25 <= len(sends) <= 60                 # ~4 req/s for 10 s
    closed = spec.generator("closed_loop")(dict(MIX, clients=3), 1, 1000)
    closed.start(0.0)
    assert len(closed.due(5.0)) == 3
    assert closed.due(6.0) == []                  # nobody finished
    closed.completed(1, 7.0)
    (nxt,) = closed.due(7.5)
    assert nxt.due == 7.0 and nxt.rid == 3
