"""The traffic generator: the same seed gives the same traffic."""

import numpy as np

import generate

TREES = {"shape": "random_binary_tree", "mu": 2.85, "sigma": 0.5,
         "min": 2, "max": 56}
CHAINS = {"shape": "chain", "mu": 3.0, "sigma": 0.5, "min": 4, "max": 64}


def _draw(seed, spec, n=50):
    rng = np.random.default_rng(seed)
    s = generate.structures(spec, n, rng)
    x = generate.inputs([len(t) for t in s], 8, rng, 0.5)
    return s, x, generate.poisson_arrivals(30.0, 10.0, rng)


def test_same_seed_same_traffic():
    big = 3_000_000_123                      # above 32 signed bits
    for spec in (TREES, CHAINS):
        a, b = _draw(big, spec), _draw(big, spec)
        assert a[0] == b[0]
        assert all(np.array_equal(u, v) for u, v in zip(a[1], b[1]))
        assert np.array_equal(a[2], b[2])
        c = _draw(big + 1, spec)
        assert a[0] != c[0] or not np.array_equal(a[2], c[2])


def test_sizes_within_the_mix():
    s, _x, arr = _draw(7, TREES, n=400)
    leaves = [sum(1 for ch in t if not ch) for t in s]
    assert min(leaves) >= 2 and max(leaves) <= 56
    assert all(len(t) == 2 * n - 1 for t, n in zip(s, leaves))
    lens = [len(t) for t in _draw(7, CHAINS, n=400)[0]]
    assert min(lens) >= 4 and max(lens) <= 64
    # Every run sends the same number of requests, in the window.
    assert len(arr) == 300 and arr.min() >= 0 and arr.max() < 10.0
    assert np.all(np.diff(arr) >= 0)


def test_structures_are_topological():
    rng = np.random.default_rng(3)
    for t in generate.structures(TREES, 20, rng):
        lv = generate.levels(t)
        assert generate.root(t) == len(t) - 1
        assert lv[-1] == lv.max()
    cat = generate.caterpillar_tree(5)
    assert generate.levels(cat).max() + 1 == 5


def test_seed_key_fits_prng():
    assert 0 <= generate.seed_key(2 ** 31 + 17) < 2 ** 31 - 1
    assert generate.seed_key(5) == generate.seed_key(5)


def test_sst_lengths_match_the_source():
    # SST sentences average 18 to 19 tokens (Kim 2014, Table 1).
    leaves = generate.sizes(TREES, 8544)
    assert 18.0 <= leaves.mean() <= 19.5
    assert leaves.min() == 2 and leaves.max() == 56


def test_corpus_fixed_order_from_seed():
    tr = {"structure": TREES, "corpus_seed": 5}
    a = generate.corpus(tr, 40, np.random.default_rng(1))
    b = generate.corpus(tr, 40, np.random.default_rng(2))
    key = lambda t: str(t)  # noqa: E731
    assert a != b and sorted(a, key=key) == sorted(b, key=key)


def test_arrivals_same_gaps_in_another_order():
    a = generate.poisson_arrivals(30.0, 10.0, np.random.default_rng(1))
    b = generate.poisson_arrivals(30.0, 10.0, np.random.default_rng(2))
    assert len(a) == len(b) == 300 and not np.array_equal(a, b)
    gaps = lambda x: np.sort(np.diff(np.append(x, 10.0)))  # noqa: E731
    assert np.allclose(gaps(a), gaps(b))
    # exponential gaps: as many under the mean as a Poisson process has
    assert abs(np.mean(gaps(a) < 1 / 30.0) - (1 - np.exp(-1))) < 0.01
