"""Every cell of BENCHMARK.json resolves its configuration, traffic and
metric readers by name, and the file keeps to its contract's shape."""
import os
import re
import subprocess
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import spec  # noqa: E402

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    c = spec.resolve(cell)
    assert c.chips in (1, 4)
    assert c.config["name"] in {x["name"] for x in BENCH["configs"]}
    assert c.traffic["rate_per_s"] > 0
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(spec.load_reader(m["name"]))
    # held to exactly the numbers its mix's check returns (for an empty
    # window too: the keys do not depend on the items)
    numbers = spec.load_mix(c.mix).compare([], [], SimpleNamespace(
        config=c.config, seed=0, after={},
        vectors=np.zeros((0, c.config["dim"]), np.float32)))
    assert set(c.config["limits"]) == set(numbers)


def test_names_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    moved = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in moved for m in BENCH["per_layer"])
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25


def test_unknown_workload():
    with pytest.raises(KeyError):
        spec.resolve("no-such-cell")


def test_run_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""
