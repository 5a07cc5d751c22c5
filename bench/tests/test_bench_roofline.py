"""The dense kernel's work count: a hand count for f32 and int8, and a
count that depends only on the live rows of the namespaces a call queries
(never on capacity or the kernel's block size)."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import pytest  # noqa: E402

import roofline  # noqa: E402
import spec  # noqa: E402

PEAKS = spec.load_peaks("TPU v5 lite")


@pytest.mark.parametrize("quantize,elem,scale", [("none", 4, 0),
                                                  ("int8", 1, 4)])
def test_call_cost_hand_count(quantize, elem, scale):
    # 3 queries: two on namespace 7 (1,024 rows), one on namespace 9 (10)
    flops, nbytes = roofline.call_cost({7: 2, 9: 1}, {7: 1024, 9: 10},
                                       dim=256, quantize=quantize, k=64)
    assert flops == 2 * 256 * (2 * 1024 + 10)
    rows = 1024 + 10                       # each namespace read once
    assert nbytes == rows * (256 * elem + scale) + 3 * 256 * 4 + 3 * 64 * 8


def test_least_time_names_its_bound():
    t, bound = roofline.least_time(1e6, 819e9, "none", PEAKS)
    assert bound == "memory" and t == pytest.approx(1.0)
    t, bound = roofline.least_time(197e12, 1.0, "none", PEAKS)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, _ = roofline.least_time(393e12, 1.0, "int8", PEAKS)
    assert t == pytest.approx(1.0)


@pytest.mark.parametrize("capacity,block_n", [(1 << 21, 512), (1 << 22, 512),
                                              (1 << 21, 1024)])
def test_count_ignores_capacity_and_block(capacity, block_n):
    """A config that doubles the bank's capacity or changes the kernel's
    block leaves the count alone: only live rows of queried namespaces."""
    cfg = {"dim": 256, "quantize": "int8", "pool": 64,
           "capacity": capacity, "block_n": block_n}
    calls = [{0: 3, 5: 1}, {5: 2}]
    live = {0: 1024, 5: 1024, 6: 1 << 20}     # 6 is never queried
    t, bound = roofline.window_least_time(
        calls, live, cfg["dim"], cfg["quantize"], cfg["pool"], PEAKS)
    rows_read = 2 * 1024 + 1024
    io = 6 * 256 * 4 + 6 * 64 * 8
    assert bound == "memory"
    assert t == pytest.approx((rows_read * (256 + 4) + io) / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        spec.load_peaks("cpu")
