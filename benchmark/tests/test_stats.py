from benchmark.harness import stats


def test_percentiles_on_fixed_arrays():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 5.0
    assert stats.percentile(xs, 90) == 4.6
    assert stats.percentile([7.0], 90) == 7.0
    hundred = [float(i) for i in range(1, 101)]
    assert stats.percentile(hundred, 50) == 50.5
    assert abs(stats.percentile(hundred, 90) - 90.1) < 1e-9


def test_sample_counts():
    assert stats.samples_beyond(100, 90) == 10
    assert stats.samples_beyond(5, 90) == 0
    assert stats.samples_beyond(200, 95) == 10


def test_call_seeds_fit_the_cli_and_differ():
    big = 2 ** 31 + 12345
    seeds = [stats.call_seed(big, i) for i in range(200)]
    assert len(set(seeds)) == 200
    assert all(0 <= s < 2 ** 31 - 1 for s in seeds)
    assert seeds == [stats.call_seed(big, i) for i in range(200)]
    assert stats.call_seed(big + 1, 0) != seeds[0]
