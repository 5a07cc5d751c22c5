"""Quickstart: the Memori persistent memory layer in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py

Ingest two chat sessions through Advanced Augmentation, answer questions
from the structured memory (and compare the token bill against stuffing
the full history into the prompt) — then lose the process and come back:
the service runs on a lifecycle runtime journaling every flush to a
write-ahead log, so a brand-new process recovers the exact same memory
with `MemoryService.recover` and answers identically.
"""
import tempfile
import time

from repro.core import LifecyclePolicy, MemoryService, Message
from repro.core.baselines import FullContextMemory
from repro.core.embedder import HashEmbedder

QUESTIONS = ["What does Ana work as now?",
             "What is the name of Ana's parrot?",
             "Where did Ben travel to?"]


def main():
    data_dir = tempfile.mkdtemp(prefix="memori-quickstart-")
    # the runtime owns everything between requests: durable WAL, background
    # flusher (drains the queue in ONE batched embed call), auto-compaction
    # and snapshot rotation — no manual flush() loops anywhere below
    policy = LifecyclePolicy(flush_interval_s=0.2, max_pending=64,
                             compact_tombstone_ratio=0.3)
    memory = MemoryService(HashEmbedder(), budget=1300,
                           policy=policy, data_dir=data_dir)
    full = FullContextMemory()

    t0 = time.time() - 14 * 86400
    sessions = {
        "s0": [
            Message("Ana", "Hey! Long time no see.", t0),
            Message("Ana", "I work as a data analyst these days.", t0),
            Message("Ana", "My favorite food is pad thai.", t0),
            Message("Ana", "I adopted a parrot named Mochi.", t0),
            Message("Ben", "Nice! I went to Iceland. The glaciers were unreal.", t0),
        ],
        "s1": [
            Message("Ana", "Big news since last time we talked!", t0 + 7 * 86400),
            Message("Ana", "I used to work as a data analyst, but now I am a chef.",
                    t0 + 7 * 86400),
            Message("Ben", "I bought a telescope last week.", t0 + 7 * 86400),
        ],
    }
    for sid, msgs in sessions.items():
        # enqueue is O(1); the background flusher batches the extraction +
        # embedding behind the scenes (reads still see pending sessions)
        memory.enqueue("demo/c0", sid, msgs)
        full.record_session("demo", sid, msgs)

    print("memory stats:", memory.stats(), "\n")
    for q in QUESTIONS:
        ctx = memory.retrieve("demo/c0", q)
        print(f"Q: {q}")
        print(f"  retrieved {len(ctx.triples)} triples, "
              f"{len(ctx.summaries)} summaries, {ctx.token_count} tokens "
              f"(full-context would be {full.retrieve(q).token_count})")
        for t in ctx.triples[:3]:
            print(f"    {t.render()}")
        print()

    prompt, ctx = memory.answer_prompt("demo/c0", "What does Ana work as now?")
    print("--- assembled LLM prompt (truncated) ---")
    print(prompt[:600])

    # persistence: close (final flush + snapshot), then recover in what
    # would normally be a fresh process — answers are bit-identical
    before = [memory.retrieve("demo/c0", q).text for q in QUESTIONS]
    memory.close()
    recovered = MemoryService.recover(data_dir, HashEmbedder(), budget=1300)
    after = [recovered.retrieve("demo/c0", q).text for q in QUESTIONS]
    print("\n--- durability ---")
    print(f"recovered from {data_dir}")
    print("recovered answers identical:", before == after)


if __name__ == "__main__":
    main()
