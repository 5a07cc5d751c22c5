"""Memory-augmented agent serving: the full Memori stack end-to-end.

    PYTHONPATH=src python examples/agent_serve.py

A small LM is served with continuous batching behind the MemoriClient SDK,
fronted by the multi-tenant MemoryService: every user gets an isolated
namespace in one shared packed bank, chat turns retrieve structured memory
and record the exchange back through Advanced Augmentation, and the pending
queries of *all* tenants are answered in one batched retrieval (one embed
call + one namespace-masked topk_mips launch).  The service is mounted on
a lifecycle runtime: recorded sessions buffer in a bounded queue that a
background flusher drains in batched embed calls, every flush journals to
a write-ahead log in a durable directory, and `service.close()` (via the
SDK clients' `close()`) writes the final snapshot generation — restart the
process with the same directory and it recovers where it left off.  The LM
is random-init (this box trains ~minutes, not the hours a useful chat
model needs) — the demo shows the *system*: interception, retrieval,
isolation, token accounting, batched decode, durability — and, at the end,
the MemoryScheduler fusing independent concurrent clients' single retrieves into
one batched device launch per tick (continuous batching for memory ops).
"""
import tempfile
import threading
import time

import jax

from repro.configs import get_config
from repro.core import LifecyclePolicy, MemoriClient, MemoryService
from repro.core.embedder import HashEmbedder
from repro.data.tokenizer import HashTokenizer
from repro.models.model_api import Model
from repro.serving.engine import Engine
from repro.serving.sampler import SamplerConfig


def main():
    cfg = get_config("memori-agent").reduced(layers=2, d_model=128)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    tok = HashTokenizer(cfg.vocab_size)
    engine = Engine(model, params, max_len=192, slots=2,
                    sampler=SamplerConfig(temperature=0.9, top_k=50),
                    tokenizer=tok)

    def llm(prompt: str) -> str:
        return engine.generate([prompt[-600:]], max_new_tokens=16)[0]

    data_dir = tempfile.mkdtemp(prefix="memori-agent-")
    service = MemoryService(
        HashEmbedder(), budget=800,
        data_dir=data_dir,
        policy=LifecyclePolicy(flush_interval_s=0.1, max_pending=128,
                               compact_tombstone_ratio=0.3,
                               snapshot_interval_s=10.0))
    users = {
        "priya/c0": ("Priya", [
            "Hi there! I am Priya.",
            "I work as a botanist and I live in Tallinn.",
            "My favorite color is indigo.",
            "I adopted a hedgehog named Biscuit.",
        ]),
        "marco/c0": ("Marco", [
            "Hello, Marco here.",
            "I work as a glassblower and I live in Porto.",
            "I adopted a parrot named Olive.",
        ]),
    }
    for ns, (name, turns) in users.items():
        client = MemoriClient(llm, service.namespace(ns), user_name=name)
        for t in turns:
            reply = client.chat(t, timestamp=time.time())
            print(f"{name}: {t}\n  agent: {reply[:60]}")
        # end_session enqueues into the runtime's bounded queue; the
        # background flusher drains it — no manual flush loop
        client.end_session()

    print("\nservice after sessions:", service.stats())
    # the cross-tenant hot path: both tenants' queries in ONE batched call
    # (reads are read-your-writes even while sessions sit in the queue)
    batch = [("priya/c0", "What is the name of Priya's pet?"),
             ("marco/c0", "What is the name of Marco's pet?")]
    for (ns, q), ctx in zip(batch, service.retrieve_batch(batch)):
        print(f"\n[{ns}] Q: {q}  ({ctx.token_count} tokens injected)")
        for t in ctx.triples[:3]:
            print(f"   {t.render()}")

    # cross-CLIENT batching: mount the MemoryScheduler and let independent
    # threads (each a client issuing ONE retrieve at a time, the real
    # deployment shape) coalesce into one device launch per tick — no
    # caller hand-assembles a batch
    service.start_scheduler(tick_interval_s=0.01, max_batch=16)
    answers = {}

    def client(ns, q):
        # service.retrieve routes through the scheduler automatically
        answers[ns] = service.retrieve(ns, q)

    threads = [threading.Thread(target=client, args=(ns, q))
               for ns, q in batch]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    st = service.scheduler.stats()
    print(f"\nscheduler: {st['retrieves']} concurrent single retrieves in "
          f"{st['retrieve_launches']} batched launch(es)")
    print(f"engine stats: {engine.stats}")
    service.close()          # scheduler drain + final flush + snapshot
    print(f"memory durable in {data_dir} "
          f"(MemoryService.recover picks it up)")


if __name__ == "__main__":
    main()
