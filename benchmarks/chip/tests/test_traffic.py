"""Traffic is a fixed multiset in a fixed order per traffic file: the seed
changes the text, nothing else."""

import glob
import os

import pytest

import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(os.path.basename(p)[:-5]
               for p in glob.glob(os.path.join(HERE, "traffic", "*.json")))


def generators(name, rehearse):
    spec = traffic.load_traffic(name, rehearse)
    return [traffic.load_generator(spec).Generator(spec["params"], seed)
            for seed in (0, 1, 2, 3000000001)]


@pytest.mark.parametrize("name", FILES)
@pytest.mark.parametrize("rehearse", [False, True])
def test_same_multiset_and_order_under_every_seed(name, rehearse):
    gens = generators(name, rehearse)
    first = gens[0].multiset()
    assert first["pairs"] or first["gaps"]
    for g in gens[1:]:
        assert g.multiset() == first
    orders = [g.order() for g in gens]
    assert all(o == orders[0] for o in orders[1:])
    assert len(orders[0]) % traffic.BLOCK == 0
    # every block of 8 carries the same mix: one item from each eighth
    ranked = sorted(orders[0])
    if len(set(ranked)) < len(ranked):
        return  # ties: ranks are not unique
    per = len(ranked) // traffic.BLOCK
    for o in orders:
        for i in range(0, len(o), traffic.BLOCK):
            eighths = sorted(ranked.index(x) // per
                             for x in o[i:i + traffic.BLOCK])
            assert eighths == list(range(traffic.BLOCK))


@pytest.mark.parametrize("name", FILES)
def test_the_seed_changes_the_text(name):
    spec = traffic.load_traffic(name)
    a, b = (traffic.load_generator(spec).Generator(spec["params"], s)
            for s in (1, 2))
    assert a._rng.random() != b._rng.random()


def test_same_seed_same_order():
    spec = traffic.load_traffic("chat-closed")
    a, b = (traffic.load_generator(spec).Generator(spec["params"], 5)
            for _ in range(2))
    assert a.order() == b.order()


def test_quantiles_hit_their_distribution():
    lens = traffic.int_quantiles({"dist": "loguniform", "lo": 128, "hi": 1024},
                                 256)
    assert min(lens) >= 128 and max(lens) <= 1024
    assert 400 < sum(lens) / len(lens) < 460  # (hi-lo)/ln(hi/lo) = 431
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "exponential", "mean": 0.25}, 256)
