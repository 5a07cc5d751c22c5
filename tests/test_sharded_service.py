"""Sharded memory service end to end: shard-wise bank placement with
retrieval parity against the unsharded oracle, graceful degradation (a
downed shard answers empty with the `degraded` flag while survivors stay
bit-identical), the degraded flag through the scheduler and the HTTP
envelope, zero-recompile/zero-upload steady state on the sharded path, and
the kill-a-shard acceptance test: SIGKILL one shard owner mid-traffic,
lose its disk, recover bit-identically from the follower's shipped WAL
segments."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import repro.core.shards as shards_mod
from repro.checkpoint.replication import (DirectorySink,
                                          restore_missing_from_follower)
from repro.common.utils import count_compiles
from repro.core import MemoryService, Message, RetrieveRequest
from repro.core.embedder import HashEmbedder

CITIES = ["Tallinn", "Porto", "Cusco", "Oslo", "Quito", "Hanoi"]
QUERY = "Which city does the user live in?"
TS = 1700000000.0


def _svc(shards=1, **kw):
    return MemoryService(HashEmbedder(), use_kernel=False, budget=800,
                         shards=shards, **kw)


def _fill(svc, n=6):
    for i, city in enumerate(CITIES[:n]):
        svc.enqueue(f"u{i}/c0", "s0", [
            Message("U", f"I live in {city}.", TS),
            Message("U", f"I like {city} food.", TS)])
    svc.flush()
    return svc


def _queries(n=6):
    return [(f"u{i}/c0", QUERY) for i in range(n)]


def _raw_reqs(n=6):
    return [RetrieveRequest(f"u{i}/c0", QUERY,
                            stages=("dense", "sparse", "fuse"))
            for i in range(n)]


# -- placement + parity --------------------------------------------------------

def test_sharded_retrieval_parity_with_unsharded_oracle():
    base, sh = _fill(_svc()), _fill(_svc(shards=4))
    want = base.retrieve_batch(_queries())
    got = sh.retrieve_batch(_queries())
    assert [c.text for c in got] == [c.text for c in want]
    assert [c.token_count for c in got] == [c.token_count for c in want]
    # the fused ranking itself is identical, not just the rendered text.
    # Global row ids legitimately differ (sharded flushes place sessions
    # shard-major), so compare the tenant-local ranking and its scores.
    raw_want = base.execute(_raw_reqs())
    raw_got = sh.execute(_raw_reqs())
    assert [r.triple_ids for r in raw_got] == \
        [r.triple_ids for r in raw_want]
    for g, w in zip(raw_got, raw_want):
        assert g.scores == pytest.approx(w.scores, rel=1e-5)
    assert not any(r.degraded for r in raw_got)
    # placement: every live row landed in its namespace's shard
    stats = sh.store.sharded.stats()
    assert sum(stats["per_shard_rows"]) == sh.vindex.n
    for i in range(6):
        ns = f"u{i}/c0"
        tid = sh.store.tenant(ns).ns_id
        assert sh.store.shard_of_namespace(ns) == tid % 4


def test_degraded_batch_serves_survivors_bit_identically():
    svc = _fill(_svc(shards=4))
    base = [c.text for c in svc.retrieve_batch(_queries())]
    down = svc.store.shard_of_namespace("u0/c0")
    victims = [i for i in range(6)
               if svc.store.shard_of_namespace(f"u{i}/c0") == down]
    survivors = [i for i in range(6) if i not in victims]
    assert victims and survivors
    svc.set_shard_down(down)
    assert svc.store.down_shards() == [down]
    got = svc.retrieve_batch(_queries())
    raw = svc.execute(_raw_reqs())
    for i in victims:                  # empty by design, flagged, no error
        assert got[i].degraded and not got[i].triples
        assert raw[i].degraded and raw[i].row_ids == []
    for i in survivors:                # bit-identical to the healthy batch
        assert not got[i].degraded and got[i].text == base[i]
        assert not raw[i].degraded
    svc.set_shard_up(down)
    healed = svc.retrieve_batch(_queries())
    assert [c.text for c in healed] == base
    assert not any(c.degraded for c in healed)


def test_writes_accumulate_while_shard_down_and_surface_after_mark_up():
    svc = _fill(_svc(shards=4))
    down = svc.store.shard_of_namespace("u0/c0")
    svc.set_shard_down(down)
    svc.enqueue("u0/c0", "s1",
                [Message("U", "I adopted a gecko named Gex.", TS)])
    svc.flush()                        # host truth keeps absorbing writes
    assert svc.retrieve("u0/c0", "Any pets?").degraded
    svc.set_shard_up(down)
    ctx = svc.retrieve("u0/c0", "Any pets?")
    assert not ctx.degraded
    assert any(t.object == "gex" for t in ctx.triples)


def test_degraded_flag_flows_through_scheduler_responses():
    svc = _fill(_svc(shards=4))
    down = svc.store.shard_of_namespace("u0/c0")
    sched = svc.start_scheduler(tick_interval_s=0.002, max_batch=16)
    try:
        svc.set_shard_down(down)
        futs = [sched.submit(RetrieveRequest(f"u{i}/c0", QUERY))
                for i in range(6)]
        resps = [f.result(timeout=30) for f in futs]
        for i, r in enumerate(resps):
            assert r.ok, r.error
            is_victim = svc.store.shard_of_namespace(f"u{i}/c0") == down
            assert r.degraded == is_victim
            assert r.payload.degraded == is_victim
    finally:
        sched.close()


def test_degraded_flag_in_http_response_envelope():
    import urllib.request
    from repro.serving.frontend import MemoryFrontend

    svc = _svc(shards=2)
    fe = MemoryFrontend(svc, {"key-acme": "acme", "key-beta": "beta"}).start()

    def call(path, body, key):
        req = urllib.request.Request(
            fe.address + path, data=json.dumps(body).encode(),
            headers={"Authorization": f"Bearer {key}"})
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read().decode())

    try:
        for key, city in (("key-acme", "Lisbon"), ("key-beta", "Quito")):
            call("/v1/record", {
                "namespace": "conv0", "session_id": "s0",
                "messages": [{"speaker": "U", "text": f"I live in {city}.",
                              "timestamp": TS}]}, key)
        ns_beta = next(n for n in svc.namespaces() if n.startswith("beta"))
        ns_acme = next(n for n in svc.namespaces() if n.startswith("acme"))
        down = svc.store.shard_of_namespace(ns_beta)
        assert svc.store.shard_of_namespace(ns_acme) != down
        svc.set_shard_down(down)
        q = {"namespace": "conv0", "query": QUERY}
        beta = call("/v1/retrieve", q, "key-beta")
        acme = call("/v1/retrieve", q, "key-acme")
        assert beta["status"] == "ok" and beta["degraded"] is True
        assert beta["payload"]["degraded"] is True
        assert beta["payload"]["triples"] == []
        assert acme["degraded"] is False
        assert any("lisbon" in t["object"]
                   for t in acme["payload"]["triples"])
    finally:
        fe.close()


# -- residency guarantees on the sharded path ----------------------------------

def test_sharded_steady_state_no_recompile_no_bank_upload(monkeypatch):
    """Once warm, the sharded flush -> scatter -> search cycle mints zero
    executables and moves no bank-sized buffers host->device: sharding
    must not regress the single-device residency guarantees."""
    svc = _fill(_svc(shards=4))
    qs = _queries()
    svc.retrieve_batch(qs)             # first search: rebuild + compile
    for i in range(2):                 # warm the append/scatter pads
        svc.enqueue("u0/c0", f"w{i}", [Message("U", "I like Oslo food.", TS)])
        svc.flush()
        svc.retrieve_batch(qs)
    sb = svc.store.sharded
    assert not sb.stale
    slab = sb.n_slots * sb.dim * 4     # full-bank upload size, bytes
    uploads = []
    real_asarray = shards_mod.jnp.asarray

    def spy_asarray(x, *a, **kw):
        if getattr(x, "nbytes", 0) >= slab:
            uploads.append(np.shape(x))
        return real_asarray(x, *a, **kw)

    monkeypatch.setattr(shards_mod.jnp, "asarray", spy_asarray)
    with count_compiles() as cc:
        for i in range(5):
            svc.enqueue("u0/c0", f"x{i}",
                        [Message("U", "I like Oslo food.", TS)])
            svc.flush()
            got = svc.retrieve_batch(qs)
            assert len(got) == 6
    assert cc.count == 0, f"recompiled {cc.count}x: {cc.msgs[:3]}"
    assert uploads == [], f"bank-sized host->device transfers: {uploads}"


@pytest.mark.slow
def test_sharded_bank_spans_all_mesh_devices_with_parity():
    """shards=8 over a (4, 2) CPU device mesh: the device bank is laid out
    across all 8 devices and answers exactly like the single-device
    service.  Subprocess so the pytest parent keeps its one CPU device."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.core import MemoryService, Message
        from repro.core.embedder import HashEmbedder
        from repro.launch.mesh import make_mesh

        cities = ["Tallinn", "Porto", "Cusco", "Oslo", "Quito", "Hanoi",
                  "Lagos", "Lima"]

        def fill(svc):
            for i, c in enumerate(cities):
                svc.enqueue("u%d/c0" % i, "s0",
                            [Message("U", "I live in %s." % c, 1700000000.0)])
            svc.flush()
            return svc

        mesh = make_mesh((4, 2), ("data", "model"))
        svc = fill(MemoryService(HashEmbedder(), use_kernel=False,
                                 budget=800, shards=8, mesh=mesh))
        queries = [("u%d/c0" % i, "Which city does the user live in?")
                   for i in range(8)]
        texts = [c.text for c in svc.retrieve_batch(queries)]
        bank = svc.store.sharded.bank_device()
        assert len(bank.sharding.device_set) == 8, bank.sharding
        ref = fill(MemoryService(HashEmbedder(), use_kernel=False,
                                 budget=800))
        assert texts == [c.text for c in ref.retrieve_batch(queries)]
        print("MESH_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert "MESH_OK" in out.stdout, out.stderr[-2000:]


# -- the acceptance test: kill a shard owner, recover from the follower --------

_KILL_CHILD = r"""
import hashlib, json, os, sys, time
import numpy as np
from repro.core import MemoryService, Message
from repro.core.embedder import HashEmbedder

d = sys.argv[1]
svc = MemoryService(HashEmbedder(), use_kernel=False, shards=2,
                    data_dir=os.path.join(d, "data"))
svc.attach_follower(os.path.join(d, "follower"))   # sync segment shipping
cities = ["Tallinn", "Porto", "Cusco", "Oslo", "Quito", "Hanoi"]
for i, city in enumerate(cities):
    ns = "u%d/c0" % i
    svc.enqueue(ns, "s0", [
        Message("U", "I live in %s." % city, 1700000000.0),
        Message("U", "I adopted a gecko named G%d." % i, 1700000000.0)])
    svc.flush()          # durable: shard parts + cross-shard commit record
    if i == 1:
        svc.rotate()     # mid-stream snapshot + shard-segment GC
    queries = [("u%d/c0" % j, "Which city does the user live in?")
               for j in range(i + 1)]
    texts = [c.text for c in svc.retrieve_batch(queries)]
    bank = np.ascontiguousarray(svc.vindex.bank)
    exp = {"n": i + 1, "texts": texts, "bank_rows": int(bank.shape[0]),
           "bank_sha": hashlib.sha256(bank.tobytes()).hexdigest()}
    tmp = os.path.join(d, "expected.json.tmp")
    with open(tmp, "w") as f:
        json.dump(exp, f); f.flush(); os.fsync(f.fileno())
    os.replace(tmp, os.path.join(d, "expected.json"))
    print("FLUSHED %d" % (i + 1), flush=True)
print("DONE", flush=True)
time.sleep(60)
"""


def test_kill_a_shard_recovery_from_follower_bit_identical(tmp_path):
    """SIGKILL the sharded writer mid-soak, then lose shard 1's disk
    entirely: re-materialize it from the follower's shipped segments and
    recover — retrieval and the bank-row prefix must be bit-identical to
    the writer's last durable commit.  Surviving-shard tenants answer
    (flagged degraded) even while the shard is marked down."""
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILL_CHILD, str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={"PATH": os.environ.get("PATH", ""), "PYTHONPATH": "src",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    deadline = time.time() + 180
    killed = False
    try:
        for line in iter(proc.stdout.readline, ""):
            if line.startswith("FLUSHED") and int(line.split()[1]) >= 4:
                proc.kill()            # SIGKILL: no atexit, no final ship
                killed = True
                break
            if time.time() > deadline:
                break
    finally:
        if not killed:
            proc.kill()
        proc.wait()
    assert killed, f"writer never reached 4 flushes: {proc.stderr.read()}"

    with open(str(tmp_path / "expected.json")) as f:
        exp = json.load(f)
    assert exp["n"] >= 4
    data = str(tmp_path / "data")
    shutil.rmtree(os.path.join(data, "shard-01"))   # the shard's disk dies
    sink = DirectorySink(str(tmp_path / "follower"))
    restored = restore_missing_from_follower(sink, data)
    assert any(r.startswith("shard-01/") for r in restored), restored

    svc = MemoryService.recover(data, HashEmbedder(), use_kernel=False,
                                budget=800)
    assert svc.store.shards == 2                    # autodetected layout
    queries = [(f"u{j}/c0", QUERY) for j in range(exp["n"])]
    got = [c.text for c in svc.retrieve_batch(queries)]
    assert got == exp["texts"]
    bank = np.ascontiguousarray(svc.vindex.bank[: exp["bank_rows"]])
    assert svc.vindex.n >= exp["bank_rows"]
    assert hashlib.sha256(bank.tobytes()).hexdigest() == exp["bank_sha"]

    # degraded serving: with shard 1 marked down, shard-0 tenants answer
    # bit-identically and shard-1 tenants are flagged, not failed
    svc.set_shard_down(1)
    dg = svc.retrieve_batch(queries)
    for j in range(exp["n"]):
        if svc.store.shard_of_namespace(f"u{j}/c0") == 1:
            assert dg[j].degraded and not dg[j].triples
        else:
            assert not dg[j].degraded and dg[j].text == exp["texts"][j]
    svc.set_shard_up(1)
    assert [c.text for c in svc.retrieve_batch(queries)] == exp["texts"]
