"""Open-loop HTTP load generator, run as a child process that never imports
JAX (the server process holds the chip).

Reads one JSON object on stdin:

    {"address": "http://127.0.0.1:PORT", "api_key": ..., "workers": N,
     "drain_s": 60, "items": [schedule entries from bench/traffic.py]}

sends every item's /v1/retrieve when it falls due, and writes
one JSON object to stdout: the window's start on the host's unix clock,
and per request its status, its latency timed from when it was due, how
late the generator sent it, the server's queued_s/service_s, and the
answer fields the correctness check reads.
"""
from __future__ import annotations

import http.client
import json
import queue
import sys
import threading
import time
import urllib.parse


def _answer(env: dict) -> dict:
    p = env.get("payload") or {}
    if p.get("kind") == "raw_retrieval":
        return {"row_ids": p["row_ids"], "scores": p["scores"]}
    return {}


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
            due = t0 + it["due"]
            sent = time.monotonic()
            res = {"i": it["i"], "late_s": sent - due}
            try:
                code, env = cli.post("/v1/retrieve", it["body"],
                                     f"r{it['i']}")
                done = time.monotonic()
                res.update(status=code, latency_s=done - due,
                           queued_s=env.get("queued_s"),
                           service_s=env.get("service_s"),
                           batch_size=env.get("batch_size"),
                           ok=code == 200 and env.get("status") == "ok",
                           **_answer(env))
            except Exception as e:                 # noqa: BLE001
                res.update(status=-1, ok=False, error=repr(e)[:200])
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
               {"i": it["i"], "status": -1, "ok": False, "error": "missing"}
               for r, it in zip(results, items)]
    return {"t0_unix": t0_unix, "end_unix": end_unix,
            "window_s": float(job.get("window_s", 0.0)),
            "retrieves": out}


def main() -> int:
    job = json.load(sys.stdin)
    json.dump(run(job), sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
