"""ShardedBank — shard-wise device placement of the memory bank.

The single-device `VectorIndex` packs rows in append order; this module
re-lays the LIVE rows out **shard-major** so the bank can be placed over a
device mesh and searched by the namespace-masked `sharded_topk` in one
launch.  Placement is namespace-affine — shard = ns_id % n_shards — so a
tenant's rows live together on one shard: losing a shard degrades a known
subset of tenants instead of a random subset of every tenant's memory, and
marking the shard down is one label-slab write.

Layout: shard `s` owns the slot range `[s*C, (s+1)*C)` for a uniform pow2
per-shard capacity `C`, so the flattened `(S*C, D)` bank divides evenly
over the mesh's bank axes (`common/partitioning.py` "bank" rules) and each
device holds whole shards' slabs.  The total device bank is `S*C` rows —
with S shards on S devices this is the "8x beyond single-device capacity"
shape: each device materializes only its `(C, D)` slab.

Three host arrays mirror the device state: the slab-packed bank, the
per-slot namespace labels (-1 = empty/tombstone), and the slot -> global
row map.  Search returns device (scores, slots); slots map back to global
row ids with one tiny O(Q*k) host gather — no device gather, no extra
collective, and the row-id space stays identical to the unsharded path.

Steady state mirrors the VectorIndex contract: appends scatter into live
device buffers in place (pow2-padded widths, bounded executables, no bank
re-upload), deletes scatter -1 labels, and only capacity growth or
compaction re-uploads.  A down shard is a `(C,)` label-slab write of -1 —
retrieval keeps answering from the surviving shards (the service stamps
those responses `degraded`); `mark_up` writes the real labels back.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Set

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.utils import next_pow2
from repro.core.vector_index import _search_device, sharded_topk
from repro.kernels import ops as kops
from repro.obs.telemetry import get_telemetry

MIN_SHARD_CAPACITY = 64


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _dev_scatter(bank, labels, slots, vecs, ns):
    return bank.at[slots].set(vecs), labels.at[slots].set(ns)


@functools.partial(jax.jit, donate_argnums=(0,))
def _dev_set_slab(labels, slab, start):
    return jax.lax.dynamic_update_slice(labels, slab, (start,))


class ShardedBank:
    def __init__(self, dim: int, n_shards: int, mesh=None,
                 use_kernel: bool = True):
        if n_shards < 2:
            raise ValueError("ShardedBank needs n_shards >= 2")
        self.dim = dim
        self.n_shards = int(n_shards)
        self.mesh = mesh
        self.use_kernel = use_kernel
        self.C = MIN_SHARD_CAPACITY          # per-shard slot capacity (pow2)
        self.down: Set[int] = set()
        # stale=True until rebuild(): the bank starts life re-derived from
        # the VectorIndex host mirror (the ground truth), and falls back to
        # stale after compaction re-packs the global row-id space
        self.stale = True
        self._alloc_host()
        self._slot_of_row = np.full((0,), -1, np.int64)
        self._count = np.zeros((self.n_shards,), np.int64)
        self._bank_dev = None
        self._labels_dev = None
        self._mesh_fns = {}                  # k -> jitted sharded_topk
        self.counters = {"rebuilds": 0, "grows": 0, "searches": 0}

    # -- host layout ---------------------------------------------------------
    @property
    def n_slots(self) -> int:
        return self.n_shards * self.C

    def _alloc_host(self) -> None:
        self._bank_host = np.zeros((self.n_slots, self.dim), np.float32)
        self._labels_host = np.full((self.n_slots,), -1, np.int32)
        self._rows_host = np.full((self.n_slots,), -1, np.int32)

    def shard_of(self, ns_id: int) -> int:
        return int(ns_id) % self.n_shards

    def invalidate(self) -> None:
        """Global row ids moved (compaction) — the layout must be re-derived
        from the VectorIndex before the next search."""
        self.stale = True
        self._bank_dev = None
        self._labels_dev = None

    def rebuild(self, vindex) -> None:
        """Re-derive the shard-major layout from the index's host mirror:
        live rows only, packed per shard in global-row order (deterministic,
        so two replicas that replayed the same WAL lay out identically)."""
        n = vindex.n
        ns = np.asarray(vindex.row_namespaces(), np.int32)
        alive = np.asarray(vindex.alive(), bool) if n else \
            np.zeros((0,), bool)
        shard = ns % self.n_shards if n else np.zeros((0,), np.int64)
        counts = np.bincount(shard[alive], minlength=self.n_shards) if n \
            else np.zeros((self.n_shards,), np.int64)
        self.C = max(MIN_SHARD_CAPACITY,
                     next_pow2(int(counts.max()) if n else 0))
        self._alloc_host()
        self._slot_of_row = np.full((n,), -1, np.int64)
        self._count = np.zeros((self.n_shards,), np.int64)
        bank = vindex.bank
        for s in range(self.n_shards):
            rows = np.nonzero(alive & (shard == s))[0]
            cnt = rows.size
            if cnt:
                slots = s * self.C + np.arange(cnt)
                self._bank_host[slots] = bank[rows]
                self._labels_host[slots] = ns[rows]
                self._rows_host[slots] = rows
                self._slot_of_row[rows] = slots
            self._count[s] = cnt
        self.stale = False
        self._bank_dev = None
        self._labels_dev = None
        self.counters["rebuilds"] += 1

    def _grow(self, need: int) -> None:
        new_c = next_pow2(int(need))
        old_c, S = self.C, self.n_shards
        old_bank, old_labels, old_rows = (self._bank_host, self._labels_host,
                                          self._rows_host)
        self.C = new_c
        self._alloc_host()
        for s in range(S):
            cnt = int(self._count[s])
            if cnt:
                self._bank_host[s * new_c: s * new_c + cnt] = \
                    old_bank[s * old_c: s * old_c + cnt]
                self._labels_host[s * new_c: s * new_c + cnt] = \
                    old_labels[s * old_c: s * old_c + cnt]
                self._rows_host[s * new_c: s * new_c + cnt] = \
                    old_rows[s * old_c: s * old_c + cnt]
        live = self._slot_of_row >= 0
        old_slots = self._slot_of_row[live]
        self._slot_of_row[live] = (old_slots // old_c) * new_c \
            + old_slots % old_c
        self._bank_dev = None                # re-upload once per doubling
        self._labels_dev = None
        self.counters["grows"] += 1

    # -- writes --------------------------------------------------------------
    def append(self, rows, vecs, ns_ids) -> None:
        """Mirror a VectorIndex append into the shard layout.  No-op while
        stale (the next rebuild sees the rows in the host mirror anyway).
        Device buffers update in place with pow2-padded scatter widths."""
        if self.stale:
            return
        rows = np.asarray(rows, np.int64).ravel()
        if rows.size == 0:
            return
        vecs = np.asarray(vecs, np.float32).reshape(rows.size, self.dim)
        ns = np.asarray(ns_ids, np.int32).ravel()
        shard = ns % self.n_shards
        need = self._count + np.bincount(shard, minlength=self.n_shards)
        if int(need.max()) > self.C:
            self._grow(int(need.max()))
        slots = np.empty((rows.size,), np.int64)
        for s in range(self.n_shards):
            m = shard == s
            cnt = int(m.sum())
            if cnt:
                slots[m] = s * self.C + int(self._count[s]) + np.arange(cnt)
                self._count[s] += cnt
        self._bank_host[slots] = vecs
        self._labels_host[slots] = ns
        self._rows_host[slots] = rows
        hi = int(rows.max()) + 1
        if hi > self._slot_of_row.shape[0]:
            grown = np.full((hi,), -1, np.int64)
            grown[: self._slot_of_row.shape[0]] = self._slot_of_row
            self._slot_of_row = grown
        self._slot_of_row[rows] = slots
        if self._bank_dev is not None:
            # a down shard's device labels stay -1 (its host truth keeps
            # accumulating; mark_up rewrites the slab)
            ns_dev = np.where(np.isin(shard, list(self.down)), -1, ns) \
                if self.down else ns
            self._scatter_dev(slots, vecs, ns_dev)

    def delete(self, rows) -> None:
        """Tombstone rows in the shard layout (slots are not reused — the
        next rebuild re-packs)."""
        if self.stale:
            return
        rows = np.asarray(rows, np.int64).ravel()
        rows = rows[(rows >= 0) & (rows < self._slot_of_row.shape[0])]
        slots = self._slot_of_row[rows]
        slots = slots[slots >= 0]
        if slots.size == 0:
            return
        self._bank_host[slots] = 0.0
        self._labels_host[slots] = -1
        self._rows_host[slots] = -1
        self._slot_of_row[rows] = -1
        if self._bank_dev is not None:
            self._scatter_dev(slots,
                              np.zeros((slots.size, self.dim), np.float32),
                              np.full((slots.size,), -1, np.int32))

    def _scatter_dev(self, slots, vecs, ns) -> None:
        m = slots.size
        pad = next_pow2(m)
        if pad > m:        # duplicate trailing slot: idempotent scatter
            slots = np.concatenate(
                [slots, np.full((pad - m,), slots[-1], np.int64)])
            vecs = np.concatenate([vecs, np.repeat(vecs[-1:], pad - m, 0)])
            ns = np.concatenate([ns, np.full((pad - m,), ns[-1], np.int32)])
        self._bank_dev, self._labels_dev = _dev_scatter(
            self._bank_dev, self._labels_dev, jnp.asarray(slots),
            jnp.asarray(vecs), jnp.asarray(ns))

    # -- shard liveness ------------------------------------------------------
    def mark_down(self, shard: int) -> None:
        """Take a shard out of retrieval: its device label slab goes to -1
        (the namespace mask hides every row) while the host truth is kept —
        this is the graceful-degradation switch, one (C,) slab write."""
        if not 0 <= shard < self.n_shards:
            raise ValueError(f"shard {shard} of {self.n_shards}")
        if shard in self.down:
            return
        self.down.add(shard)
        if self._labels_dev is not None:
            slab = jnp.asarray(np.full((self.C,), -1, np.int32))
            self._labels_dev = _dev_set_slab(self._labels_dev, slab,
                                             jnp.int32(shard * self.C))

    def mark_up(self, shard: int) -> None:
        """Bring a shard back: rewrite its label slab from host truth (a
        (C,) upload — a recovery event, not steady state)."""
        if shard not in self.down:
            return
        self.down.discard(shard)
        if self._labels_dev is not None:
            slab = jnp.asarray(
                self._labels_host[shard * self.C: (shard + 1) * self.C])
            self._labels_dev = _dev_set_slab(self._labels_dev, slab,
                                             jnp.int32(shard * self.C))

    # -- device residency ----------------------------------------------------
    def _effective_labels(self) -> np.ndarray:
        if not self.down:
            return self._labels_host
        eff = self._labels_host.copy()
        for s in self.down:
            eff[s * self.C: (s + 1) * self.C] = -1
        return eff

    def _ensure_device(self) -> None:
        if self._bank_dev is not None:
            return
        eff = self._effective_labels()
        if self.mesh is not None:
            from repro.common.partitioning import standard_rules
            n_dev = int(np.prod(list(self.mesh.shape.values())))
            if self.n_slots % n_dev != 0:
                raise ValueError(
                    f"{self.n_slots} slots do not divide over {n_dev} mesh "
                    "devices")
            rules = standard_rules(self.mesh)
            self._bank_dev = jax.device_put(
                self._bank_host,
                rules.sharding_for(("bank", None), (self.n_slots, self.dim)))
            self._labels_dev = jax.device_put(
                np.ascontiguousarray(eff),
                rules.sharding_for(("bank",), (self.n_slots,)))
        else:
            self._bank_dev = jnp.asarray(self._bank_host)
            self._labels_dev = jnp.asarray(eff)

    def bank_device(self):
        """The live device bank (tests assert its sharding layout)."""
        self._ensure_device()
        return self._bank_dev

    def _mesh_fn(self, k: int):
        fn = self._mesh_fns.get(k)
        if fn is None:
            mesh, uk = self.mesh, self.use_kernel
            axes = tuple(a for a in ("pod", "data", "model")
                         if a in mesh.axis_names)

            def run(bank, labels, q, qns):
                return sharded_topk(q, bank, k, mesh, axis_names=axes,
                                    q_ns=qns, bank_ns=labels, use_kernel=uk)
            fn = self._mesh_fns[k] = jax.jit(run)
        return fn

    # -- search --------------------------------------------------------------
    def search(self, queries, q_ns, k: int):
        """One namespace-masked top-k launch over the sharded bank.
        Returns (scores (Q,k) DEVICE f32, rows (Q,k) HOST i32 global ids,
        -1 for empty).  Requires a non-stale layout (`rebuild` first)."""
        if self.stale:
            raise RuntimeError("ShardedBank is stale; rebuild() first")
        queries = jnp.asarray(queries, jnp.float32)
        if queries.ndim == 1:
            queries = queries[None]
        Q = queries.shape[0]
        if int(self._count.sum()) == 0:
            return (jnp.full((Q, k), -jnp.inf, jnp.float32),
                    np.full((Q, k), -1, np.int32))
        self._ensure_device()
        self.counters["searches"] += 1
        q_ns = jnp.asarray(q_ns, jnp.int32)
        kk = min(k, self.n_slots)
        if self.mesh is not None:
            s, i = self._mesh_fn(kk)(self._bank_dev, self._labels_dev,
                                     queries, q_ns)
        else:
            s, i, _ = _search_device(self._bank_dev, self._labels_dev,
                                     queries, q_ns, jnp.int32(self.n_slots),
                                     jnp.int32(0), k=kk,
                                     use_kernel=self.use_kernel,
                                     interpret=kops._interpret_default(),
                                     uniform=False)
        if kk < k:
            s = jnp.pad(s, ((0, 0), (0, k - kk)), constant_values=-jnp.inf)
            i = jnp.pad(i, ((0, 0), (0, k - kk)), constant_values=-1)
        with get_telemetry().span("device.wait"):
            i = np.asarray(i)
        return s, self.slots_to_rows(i)

    def slots_to_rows(self, slot_ids) -> np.ndarray:
        """Map device slot ids back to global row ids: one tiny O(Q*k) host
        gather (the id space downstream — fusion, triple lookup — is the
        same as the unsharded path)."""
        i = np.asarray(slot_ids)
        safe = np.clip(i, 0, self.n_slots - 1)
        return np.where(i >= 0, self._rows_host[safe], -1).astype(np.int32)

    # -- stats ---------------------------------------------------------------
    def stats(self) -> dict:
        return {
            "n_shards": self.n_shards,
            "per_shard_capacity": self.C,
            "total_slots": self.n_slots,
            "per_shard_rows": [int(c) for c in self._count],
            "down": sorted(self.down),
            "stale": self.stale,
            "meshed": self.mesh is not None,
            **self.counters,
        }
