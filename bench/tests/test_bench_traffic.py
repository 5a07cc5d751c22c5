"""The traffic generator: the same seed gives the same schedule, every
seed the same amount of work, and large seeds work."""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import spec  # noqa: E402
import traffic  # noqa: E402

CONFIG = {"tenants": 1024}
MIXES = sorted(p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json"))


def _mix(name):
    with open(spec.BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    t = _mix(mix)
    a = traffic.schedule(t, CONFIG, 2 ** 31 + 17, 3.0)
    b = traffic.schedule(t, CONFIG, 2 ** 31 + 17, 3.0)
    assert json.dumps(a) == json.dumps(b)
    c = traffic.schedule(t, CONFIG, 2 ** 31 + 18, 3.0)
    assert json.dumps(a) != json.dumps(c)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_same_count_inside_window(mix):
    t = _mix(mix)
    n = round(t["rate_per_s"] * 2.0)
    for seed in (0, 1, 5_000_000_000):
        s = traffic.schedule(t, CONFIG, seed, 2.0)
        due = np.array([x["due"] for x in s])
        assert len(s) == n
        assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 2.0
        assert all(0 <= x["tenant"] < CONFIG["tenants"] for x in s)


def test_zipf_skew():
    rng = np.random.default_rng(3)
    ten = traffic.zipf_tenants(rng, 1024, 0.99, 20000)
    counts = np.sort(np.bincount(ten, minlength=1024))[::-1]
    # Zipf(0.99) over 1,024 ranks: the hottest tenant takes ~13%
    assert 0.10 < counts[0] / ten.size < 0.16
    assert counts[0] > 50 * max(1, counts[511])
