"""Open-loop HTTP load generator, run as a child process that never imports
JAX (the server process holds the chip).

Reads one JSON object on stdin:

    {"address": "http://127.0.0.1:PORT", "api_key": ..., "workers": N,
     "drain_s": 60, "items": [a mix's schedule (bench/mixes/<kind>.py)]}

An item is `{i, due, tenant, ops: [{path, body, keep}]}`.  When an item
falls due, one worker sends its ops in order on its connection, each
after the previous reply; an op that fails leaves the item's later ops
unsent.  Writes one JSON object to stdout: the window's start and end on
the host's unix clock, and per item how late the generator sent it and
per op its path, status, `ok`, its latency (the first op's timed from when
the item was due, a later op's from when it was sent), the server's
queued_s, service_s and batch_size, and the payload fields its `keep`
names, which the mix's check reads.
"""
from __future__ import annotations

import http.client
import json
import queue
import sys
import threading
import time
import urllib.parse


def _kept(env: dict, keep) -> dict:
    p = env.get("payload")
    if not isinstance(p, dict):
        return {}
    return {k: p[k] for k in keep if k in p}


def _unsent(op: dict, why: str) -> dict:
    return {"path": op["path"], "status": -1, "ok": False, "error": why}


class _Client:
    def __init__(self, address: str, api_key: str, timeout: float):
        u = urllib.parse.urlparse(address)
        self.host, self.port = u.hostname, u.port
        self.api_key = api_key
        self.timeout = timeout
        self.conn = None

    def post(self, path: str, body: dict, rid: str):
        blob = json.dumps(body).encode()
        headers = {"Content-Type": "application/json",
                   "X-Api-Key": self.api_key, "X-Request-Id": rid}
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=self.timeout)
            try:
                self.conn.request("POST", path, blob, headers)
                r = self.conn.getresponse()
                data = r.read()
                return r.status, json.loads(data or b"{}")
            except (ConnectionError, http.client.HTTPException):
                # a keep-alive connection the server closed: reconnect once
                self.conn.close()
                self.conn = None
                if attempt:
                    raise


def run(job: dict) -> dict:
    items = job["items"]
    work: "queue.Queue" = queue.Queue()
    results = [None] * len(items)
    lock = threading.Lock()
    t0 = time.monotonic() + 0.2
    t0_unix = time.time() + (t0 - time.monotonic())

    def worker():
        cli = _Client(job["address"], job["api_key"],
                      float(job.get("drain_s", 60)) + 30)
        while True:
            it = work.get()
            if it is None:
                return
            start = t0 + it["due"]
            res = {"i": it["i"], "late_s": time.monotonic() - start,
                   "ops": []}
            for j, op in enumerate(it["ops"]):
                # the first op keeps the request id r<i> that the metric
                # readers map to the item; later ops are r<i>.<j>
                rid = f"r{it['i']}.{j}" if j else f"r{it['i']}"
                if j:
                    start = time.monotonic()
                r = {"path": op["path"]}
                try:
                    code, env = cli.post(op["path"], op["body"], rid)
                    r.update(status=code,
                             latency_s=time.monotonic() - start,
                             queued_s=env.get("queued_s"),
                             service_s=env.get("service_s"),
                             batch_size=env.get("batch_size"),
                             ok=code == 200 and env.get("status") == "ok",
                             **_kept(env, op["keep"]))
                except Exception as e:             # noqa: BLE001
                    r.update(status=-1, ok=False, error=repr(e)[:200])
                res["ops"].append(r)
                if not r["ok"]:
                    res["ops"] += [_unsent(o, "not sent")
                                   for o in it["ops"][j + 1:]]
                    break
            with lock:
                results[it["i"]] = res

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(job["workers"]))]
    for t in threads:
        t.start()
    for it in items:
        wait = t0 + it["due"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        work.put(it)
    last_due = t0 + (items[-1]["due"] if items else 0.0)
    for _ in threads:
        work.put(None)
    deadline = last_due + float(job.get("drain_s", 60))
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    end_unix = time.time()
    with lock:
        out = [r if r is not None else
               {"i": it["i"], "ops": [_unsent(o, "missing")
                                      for o in it["ops"]]}
               for r, it in zip(results, items)]
    return {"t0_unix": t0_unix, "end_unix": end_unix,
            "window_s": float(job.get("window_s", 0.0)),
            "items": out}


def main() -> int:
    job = json.load(sys.stdin)
    json.dump(run(job), sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
