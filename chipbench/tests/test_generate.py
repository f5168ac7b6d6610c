"""The traffic generators: fixed sizes per seed, seeded order."""
import numpy as np

from chipbench import generate

TRAFFIC = {"rate": 4.0,
           "prompt": {"kind": "lognormal", "median": 512, "sigma": 0.8,
                      "lo": 16, "hi": 2048},
           "output": {"kind": "lognormal", "median": 120, "sigma": 0.7,
                      "lo": 8, "hi": 512}}


def test_open_loop_counts_and_window():
    reqs = generate.open_loop(TRAFFIC, 50.0, 2**31 + 5, vocab=1000)
    assert len(reqs) == 200
    due = np.array([r.due for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0) and due[-1] < 50.0
    assert all(16 <= len(r.prompt) <= 2048 for r in reqs)
    assert all(8 <= r.max_new <= 512 for r in reqs)
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000
               for r in reqs)


def test_same_sizes_other_order_across_seeds():
    a = generate.open_loop(TRAFFIC, 20.0, 1, vocab=1000)
    b = generate.open_loop(TRAFFIC, 20.0, 2, vocab=1000)
    la = [len(r.prompt) for r in a]
    lb = [len(r.prompt) for r in b]
    assert sorted(la) == sorted(lb) and la != lb
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    ga, gb = np.diff([r.due for r in a]), np.diff([r.due for r in b])
    assert abs(ga.sum() - gb.sum()) < 1e-9 * ga.sum() + 1.0
    again = generate.open_loop(TRAFFIC, 20.0, 1, vocab=1000)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               for x, y in zip(a, again))


def test_lognormal_median():
    out = generate.lengths(TRAFFIC["prompt"], 1001, generate.rng_for(0, 0))
    assert int(np.median(out)) == 512


def test_lhs_one_value_per_stratum():
    cols, codes = generate.lhs(1000, {"x": (250.0, 700.0)},
                               {"t": ("a", "b")}, 2**33 + 1)
    strata = np.floor((cols["x"] - 250.0) / 450.0 * 1000).astype(int)
    assert sorted(strata) == list(range(1000))
    assert np.bincount(codes["t"]).tolist() == [500, 500]
