"""Mixes are plug-ins found by name: the `rank` mix schedules what the
harness scheduled before mixes existed, the load generator sends an
item's ops in order, and a mix that writes (bench/tests/mixes/, a test
mix) runs through a whole cell with no edit of bench/: correct when sound,
not correct when an acknowledged write is lost."""
import http.server
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import pytest  # noqa: E402

import cell as cell_mod  # noqa: E402
import loadgen  # noqa: E402
import spec  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from test_bench_fault import tiny_cell  # noqa: E402,F401

HERE = spec.BENCH_DIR / "tests"
with open(HERE / "data" / "rank-schedules.json") as f:
    FROZEN = json.load(f)


@pytest.mark.parametrize("traffic,seed", [
    (t, s) for t in sorted(FROZEN["schedules"])
    for s in sorted(FROZEN["schedules"][t])])
def test_rank_schedule_unchanged(traffic, seed):
    """Field for field the items the harness scheduled before the move,
    each body now the one op of its item."""
    with open(spec.BENCH_DIR / "traffic" / f"{traffic}.json") as f:
        t = json.load(f)
    assert "mix" not in t
    got = spec.load_mix("rank").schedule(t, FROZEN["config"], int(seed),
                                         FROZEN["seconds"])
    want = FROZEN["schedules"][traffic][seed]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert set(g) == {"i", "due", "tenant", "ops"}
        assert (g["i"], g["due"], g["tenant"]) == (w["i"], w["due"],
                                                    w["tenant"])
        assert g["ops"] == [{"path": "/v1/retrieve", "body": w["body"],
                             "keep": ["row_ids", "scores"]}]


def test_unknown_mix():
    with pytest.raises(KeyError, match="unknown mix 'no-such-mix'.*rank"):
        spec.load_mix("no-such-mix")
    with pytest.raises(KeyError, match="retrieve_record"):
        spec.load_mix("rank", HERE / "mixes")


def test_a_retrieve_after_an_items_first_op_is_refused():
    """The readers map request id r<i>, item i's first op, to its tenant:
    a retrieve in a later op would leave their readings unseen."""
    op = {"body": {}, "keep": []}
    first = [{"i": 0, "due": 0.0, "tenant": 0, "ops": [
        dict(op, path=traffic_mod.RETRIEVE), dict(op, path="/v1/record")]}]
    traffic_mod.check_items(first)
    later = [{"i": 0, "due": 0.0, "tenant": 0, "ops": [
        dict(op, path="/v1/record"), dict(op, path=traffic_mod.RETRIEVE)]}]
    with pytest.raises(ValueError, match="item 0 has a /v1/retrieve op"):
        traffic_mod.check_items(later)


class _Stub(http.server.BaseHTTPRequestHandler):
    """/slow answers after 0.2 s, /fast at once, /fail with a 500; each
    request is logged with when it arrived and when its reply began."""
    protocol_version = "HTTP/1.1"
    log = []

    def log_message(self, *args):
        pass

    def do_POST(self):
        arrived = time.monotonic()
        self.rfile.read(int(self.headers["Content-Length"]))
        if self.path == "/slow":
            time.sleep(0.2)
        ok = self.path != "/fail"
        blob = json.dumps({
            "status": "ok" if ok else "error", "queued_s": 0.001,
            "service_s": 0.002, "batch_size": 3,
            "payload": {"row_ids": [4, 5], "scores": [0.5, 0.25],
                        "extra": 1}}).encode()
        # logged before the reply goes out, so the log is complete by the
        # time the client has its answer
        self.log.append((self.path, self.headers["X-Request-Id"], arrived,
                         time.monotonic()))
        self.send_response(200 if ok else 500)
        self.send_header("Content-Length", str(len(blob)))
        self.end_headers()
        self.wfile.write(blob)
        self.wfile.flush()


@pytest.fixture
def stub():
    _Stub.log = []
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    srv.server_close()
    t.join(timeout=10)
    assert not t.is_alive()


def _run(address, *paths):
    item = {"i": 0, "due": 0.0, "tenant": 0, "ops": [
        {"path": p, "body": {"n": j}, "keep": [["row_ids"], ["scores"]][j]}
        for j, p in enumerate(paths)]}
    out = loadgen.run({"address": address, "api_key": "k", "workers": 2,
                       "drain_s": 10.0, "items": [item]})
    assert [r["i"] for r in out["items"]] == [0]
    return out["items"][0]["ops"]


def test_loadgen_sends_an_items_ops_in_order(stub):
    first, second = _run(stub, "/slow", "/fast")
    (p0, r0, _, replied), (p1, r1, arrived, _) = _Stub.log
    assert (p0, r0, p1, r1) == ("/slow", "r0", "/fast", "r0.1")
    assert arrived >= replied
    # each op its own latency: the first from its due time, the second
    # from when it was sent
    assert first["latency_s"] >= 0.2 > second["latency_s"]
    for op, path in ((first, "/slow"), (second, "/fast")):
        assert op["ok"] and op["status"] == 200 and op["path"] == path
        assert (op["queued_s"], op["service_s"], op["batch_size"]) == \
            (0.001, 0.002, 3)
    assert first["row_ids"] == [4, 5] and "scores" not in first
    assert second["scores"] == [0.5, 0.25] and "row_ids" not in second
    assert "extra" not in first and "extra" not in second


def test_loadgen_sends_nothing_after_a_failed_op(stub):
    first, second = _run(stub, "/fail", "/fast")
    assert [p for p, *_ in _Stub.log] == ["/fail"]
    assert not first["ok"] and first["status"] == 500
    assert second == {"path": "/fast", "status": -1, "ok": False,
                      "error": "not sent"}


def _dropping(flush):
    """The store's flush with the first pending session dropped: the
    write is acknowledged but never becomes visible."""
    def drop(self):
        self._pending = self._pending[1:]
        return flush(self)
    return drop


@pytest.mark.parametrize("fault", [None, "write_dropped"])
def test_writing_mix_runs_through_a_cell(tiny_cell, monkeypatch, fault):
    from repro.core.store import MemoryStore
    build = cell_mod.build
    if fault == "write_dropped":
        monkeypatch.setattr(MemoryStore, "flush",
                            _dropping(MemoryStore.flush))
    monkeypatch.setattr(cell_mod, "load_mix",
                        lambda kind: spec.load_mix(kind, HERE / "mixes"))
    built = []
    monkeypatch.setattr(cell_mod, "build", lambda config, seed: built.append(
        build(config, seed)) or built[-1])
    tiny_cell.traffic = dict(tiny_cell.traffic, mix="retrieve_record")
    tiny_cell.config = dict(tiny_cell.config, serve_args=[
        "--max-pending", "256"], limits=dict(
        tiny_cell.config["limits"], unacked=0, unread=0))
    out = cell_mod.run(tiny_cell, 2 ** 31 + 98, 1.0, trace=False,
                       t_start=time.time())
    # the configuration's serve_args reach the served program
    assert built[0].service.runtime.policy.max_pending == 256
    assert out["attempted"] == 2 * 12 and out["failed"] == 0
    checks = out["checks"]
    assert checks["unacked"]["value"] == 0
    assert out["correct"] is (fault is None)
    if fault:
        assert checks["unread"]["value"] > 0
