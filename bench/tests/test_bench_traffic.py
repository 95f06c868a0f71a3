"""The traffic generator: exact from the seed, same work for every seed."""
import numpy as np
import pytest

from bench import traffic

MIX = {"kind": "open_loop", "rate_per_s": 40, "n_min": 16, "n_max": 1024,
       "zipf_s": 1.1, "targets": ["rvv-128", "rvv-1024"]}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_open_loop_is_exact_from_the_seed(seed):
    a = traffic.open_loop(MIX, 8, seed, 10.0)
    b = traffic.open_loop(MIX, 8, seed, 10.0)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def test_open_loop_seeds_share_the_work():
    a = traffic.open_loop(MIX, 8, 1, 10.0)
    b = traffic.open_loop(MIX, 8, 2, 10.0)
    assert len(a["n"]) == len(b["n"]) == 400
    np.testing.assert_array_equal(np.sort(a["n"]), np.sort(b["n"]))
    np.testing.assert_array_equal(np.bincount(a["item"]),
                                  np.bincount(b["item"]))
    np.testing.assert_array_equal(np.bincount(a["target"]), [200, 200])
    assert not np.array_equal(a["n"], b["n"])
    np.testing.assert_allclose(np.sort(np.diff(a["due_s"])),
                               np.sort(np.diff(b["due_s"])), rtol=0.05,
                               atol=1e-3)


def test_schedule_seed_replays_one_schedule():
    mix = dict(MIX, schedule_seed=0)
    a = traffic.open_loop(mix, 8, 1, 10.0)
    b = traffic.open_loop(mix, 8, 2**31 + 9, 10.0)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    c = traffic.open_loop(dict(MIX, schedule_seed=1), 8, 1, 10.0)
    assert not np.array_equal(a["n"], c["n"])
    np.testing.assert_array_equal(np.sort(a["n"]), np.sort(c["n"]))


def test_open_loop_shape():
    s = traffic.open_loop(MIX, 8, 3, 10.0)
    assert s["due_s"][0] == 0.0
    assert np.all(np.diff(s["due_s"]) > 0) and s["due_s"][-1] < 10.0
    assert s["n"].min() >= 16 and s["n"].max() <= 1024
    counts = np.bincount(s["item"], minlength=8)
    # Zipf(1.1): each rank at least as frequent as the next
    assert np.all(np.diff(counts) <= 0) and counts[-1] > 0
    # log-uniform sizes: as many under the geometric mean (128) as over
    assert abs(int((s["n"] < 128).sum()) - int((s["n"] > 128).sum())) <= 2


def test_validate():
    assert traffic.validate(dict(MIX)) == MIX
    with pytest.raises(ValueError, match="kind"):
        traffic.validate({"kind": "closed"})
    with pytest.raises(ValueError, match="lacks"):
        traffic.validate({"kind": "open_loop", "rate_per_s": 8})
