"""The traffic generators repeat exactly from a seed, every seed gets the
same sizes in another order, arrivals keep their Poisson bursts, and a
closed loop's preload is its steady state."""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import numpy as np
import pytest

from bench import registry

BIG = 2**31 + 987654321


def _traffic(name):
    return registry.Registry().traffic(name)


def _drain(gen, until, waiting=0):
    out = []
    t = -gen.lead_s
    while t <= until:
        out += gen.poll(t, waiting)
        t += 0.25
    return out


@pytest.mark.parametrize("name", ["code", "batch"])
def test_same_seed_same_requests(name):
    t = _traffic(name)
    mod = registry.Registry().generator(t["generator"])
    a = _drain(mod.Generator(t, BIG, 151936, 45.0), 50.0)
    b = _drain(mod.Generator(t, BIG, 151936, 45.0), 50.0)
    c = _drain(mod.Generator(t, BIG + 1, 151936, 45.0), 50.0)
    assert a == b
    assert a != c
    assert all(0 <= tok < 151936 for _, p, _ in a for tok in p)


def test_open_loop_due_count_is_fixed_by_rate_and_window():
    t = _traffic("code")
    mod = registry.Registry().generator("open_poisson")
    n = round(t["rate_per_s"] * 45.0)
    for seed in (1, BIG, 2**40 + 3):
        reqs = _drain(mod.Generator(t, seed, 1000, 45.0), 45.0)
        due = [d for d, _, _ in reqs if 0.0 <= d < 45.0]
        assert len(due) == n
        assert [d for d, _, _ in reqs] == sorted(d for d, _, _ in reqs)
        assert reqs[0][0] < 0.0  # the lead-in before the window


def test_seeds_share_the_multiset_of_lengths():
    t = _traffic("code")
    mod = registry.Registry().generator("open_poisson")
    n = round(t["rate_per_s"] * 45.0)

    def sizes(seed):
        reqs = [r for r in _drain(mod.Generator(t, seed, 1000, 45.0), 45.0) if 0.0 <= r[0] < 45.0]
        return sorted(len(p) for _, p, _ in reqs), sorted(o for _, _, o in reqs)

    assert sizes(3) == sizes(BIG)
    p, o = sizes(3)
    assert t["prompt"]["min"] <= p[0] and p[-1] <= t["prompt"]["max"]
    assert t["output"]["min"] <= o[0] and o[-1] <= t["output"]["max"]
    assert abs(np.median(p) - t["prompt"]["median"]) / t["prompt"]["median"] < 0.05


def test_closed_backlog_tops_up_the_queue():
    t = _traffic("batch")
    gen = registry.Registry().generator("closed_backlog").Generator(t, BIG, 1000, 45.0)
    assert len(gen.poll(0.0, 0)) == t["backlog"]
    assert len(gen.poll(1.0, t["backlog"] - 3)) == 3
    assert gen.poll(2.0, t["backlog"]) == []
    assert gen.closed and gen.next_due() is None


def test_open_loop_arrivals_keep_their_bursts():
    """Inside the window arrivals are uniform given their count: the number
    in each 5 s stretch varies from stretch to stretch and from seed to
    seed, as a Poisson process's does."""
    t = _traffic("code")
    mod = registry.Registry().generator("open_poisson")
    counts = []
    for seed in range(BIG, BIG + 8):
        dues = [d for d, _, _ in _drain(mod.Generator(t, seed, 1000, 45.0), 45.0) if 0.0 <= d < 45.0]
        counts.append(np.histogram(dues, bins=9, range=(0.0, 45.0))[0])
    counts = np.array(counts)
    mean = t["rate_per_s"] * 5.0
    assert counts.std() > 0.5 * np.sqrt(mean)  # Poisson: the variance is the mean
    assert len({tuple(c) for c in counts}) == len(counts)


def test_closed_backlog_preload_is_the_steady_state():
    """The preloaded slots hold the same requests at the same points of
    their lives for every seed, in another pairing; long requests are
    over-represented (a slot holds a request in proportion to its steps)."""
    t = _traffic("batch")
    mod = registry.Registry().generator("closed_backlog")

    def preload(seed):
        return mod.Generator(t, seed, 1000, 45.0).preload(32, 256)

    a, b = preload(BIG), preload(BIG + 1)
    assert len(a) == 32 and a != b
    assert all(1 <= n and len(p) + n <= t["prompt"]["max"] + t["output"]["max"] for p, n in a)
    assert sorted(len(p) + n for p, n in a) == sorted(len(p) + n for p, n in b)
    assert a == preload(BIG)
    assert np.median([len(p) + n for p, n in a]) > t["prompt"]["median"] + t["output"]["median"]
