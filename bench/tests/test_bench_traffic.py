"""The traffic generator: the same seed gives the same inputs, and a
traffic that fixes its sizes and rounds gives every seed the same work."""
import numpy as np

from bench import run, traffic


def _partition(seed, **extra):
    spec = {"partition": {"strategy": "dirichlet_quantity",
                          "samples_per_client": 6, "severity": 0.5,
                          **extra}}
    labels = np.zeros(6000, np.int32)
    return traffic.make_partition(spec, labels, seed)


def test_same_seed_same_partition():
    a, b = _partition(2**31 + 5), _partition(2**31 + 5)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_sizes_seed_fixes_sizes_not_samples():
    (ia, sa), (ib, sb) = (_partition(2**31 + 5, sizes_seed=0),
                          _partition(2**33 + 1, sizes_seed=0))
    assert np.array_equal(sa, sb)
    assert not np.array_equal(ia, ib)
    assert 1 <= sa.min() and sa.max() <= 6 and len(set(sa.tolist())) == 6
    _, sc = _partition(2**31 + 5)
    _, sd = _partition(2**33 + 1)
    assert not np.array_equal(sc, sd)


def test_rounds_seed_fixes_the_round_key():
    spec = {"traffic": {"rounds_seed": 0}}
    assert np.array_equal(run.rounds_key(spec, 1), run.rounds_key(spec, 2))
    free = {"traffic": {}}
    assert not np.array_equal(run.rounds_key(free, 1),
                              run.rounds_key(free, 2))
