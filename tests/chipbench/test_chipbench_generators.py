"""The traffic generator: the same seed gives the same traffic."""

import numpy as np
import pytest

from chipbench import registry
from chipbench.generators import arrivals

MIX = {"rate_per_s": 2000, "zipf_s": 1.1, "warm_seconds": 0.5}
BIG_SEED = 2**31 + 12345          # more than 32 signed bits hold


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_same_seed_same_schedule(seed):
    gen = registry.find("generators", "poisson_zipf")
    a = gen(MIX, seed, 3.0, 1 << 16)
    b = gen(MIX, seed, 3.0, 1 << 16)
    assert np.array_equal(a.due_s, b.due_s) and np.array_equal(a.rank, b.rank)
    assert np.array_equal(arrivals.rank_permutation(seed, 4096),
                          arrivals.rank_permutation(seed, 4096))


def test_different_seeds_differ():
    gen = registry.find("generators", "poisson_zipf")
    a, b = gen(MIX, 1, 3.0, 1 << 16), gen(MIX, 2, 3.0, 1 << 16)
    assert a.due_s.size != b.due_s.size or not np.array_equal(a.rank, b.rank)
    assert not np.array_equal(arrivals.rank_permutation(1, 4096),
                              arrivals.rank_permutation(2, 4096))


def test_arrivals_are_poisson_at_the_rate_over_warm_and_window():
    s = arrivals.poisson_zipf(MIX, 5, 20.0, 1 << 16)
    assert s.due_s[0] >= -0.5 and s.due_s[-1] < 20.0
    assert np.all(np.diff(s.due_s) > 0)
    n = (s.due_s >= 0).sum()
    assert abs(n - 40000) < 5 * np.sqrt(40000)
    gaps = np.diff(s.due_s)
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.05)


def test_zipf_is_exact_inverse_cdf_over_the_universe():
    rng = np.random.default_rng(3)
    r = arrivals.zipf_ranks(rng, 200000, 1.1, 1 << 20)
    assert r.min() == 0 and r.max() < 1 << 20
    w = 1.0 / np.arange(1, (1 << 20) + 1) ** 1.1
    assert np.mean(r == 0) == pytest.approx(w[0] / w.sum(), rel=0.05)
    assert np.mean(r < 4096) == pytest.approx(w[:4096].sum() / w.sum(),
                                              rel=0.02)


def test_permutation_maps_every_rank_to_one_key():
    p = arrivals.rank_permutation(BIG_SEED, 4096)
    assert sorted(p.tolist()) == list(range(4096))
