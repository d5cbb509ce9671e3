"""The traffic generator: the same seed gives the same requests, other
seeds the same work in another order; the check's sample is drawn from
the seed and stays bounded."""
import collections

import pytest

from chipbench import harness, traffic

BIG_SEED = 2**31 + 987654321


@pytest.fixture(scope="module")
def mix():
    return harness.cell_from(harness.load_benchmark(),
                             "stream-apps.bulk").traffic


def test_every_traffic_file_of_the_benchmark_loads():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        assert traffic.load(w["traffic"])["loop"] == "closed"


def test_cycles_are_deterministic_from_the_seed(mix):
    a = [traffic.cycle(mix, BIG_SEED, c) for c in range(8)]
    b = [traffic.cycle(mix, BIG_SEED, c) for c in range(8)]
    assert a == b
    other = [traffic.cycle(mix, BIG_SEED + 1, c) for c in range(8)]
    assert [[r.kind for r in cyc] for cyc in a] != \
        [[r.kind for r in cyc] for cyc in other]


def test_other_seeds_give_the_same_work_in_every_cycle(mix):
    want = collections.Counter(mix["cycle"])
    for seed in (1, 2, BIG_SEED):
        for c in range(5):
            assert collections.Counter(
                r.kind for r in traffic.cycle(mix, seed, c)) == want


def test_cycle_indices_continue_and_scalars_lie_in_range(mix):
    reqs = traffic.cycle(mix, 5, 3, first_index=12)
    assert [r.index for r in reqs] == list(range(12, 12 + len(reqs)))
    assert all(r.cycle == 3 and 0.5 <= r.scalar < 2.0 for r in reqs)


def test_reservoir_keeps_k_of_each_kind_drawn_from_the_seed():
    def draw(seed):
        res = traffic.Reservoir(2, seed)
        for i in range(100):
            res.offer("a" if i % 3 else "b", i)
        return res.items()

    got = draw(BIG_SEED)
    assert len(got) == 4 and got == draw(BIG_SEED)
    assert sum(1 for i in got if i % 3 == 0) == 2
    assert any(draw(s) != got for s in (1, 2, 3))


def test_reservoir_keeps_everything_below_k():
    res = traffic.Reservoir(3, 0)
    res.offer("a", 1)
    res.offer("b", 2)
    assert res.items() == [1, 2]


def test_samples_and_jax_seeds_take_any_seed():
    assert traffic.sample_indices(10, 3, BIG_SEED) == \
        traffic.sample_indices(10, 3, BIG_SEED)
    assert traffic.sample_indices(2, 5, 0) == [0, 1]
    s = traffic.jax_seed(BIG_SEED, 1)
    assert 0 <= s < 2**31 and s == traffic.jax_seed(BIG_SEED, 1)
