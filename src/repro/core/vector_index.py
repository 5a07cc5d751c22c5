"""Sharded exact-MIPS vector index — the FAISS replacement (DESIGN.md §3).

Device-resident retrieval engine: the packed bank and the per-row effective
namespace labels (namespace id for live rows, -1 for tombstones and unfilled
capacity) live in capacity-doubling **device** buffers.  `add` / `delete`
update them in place (donated `dynamic_update_slice` / scatter — no
host round-trip), so steady-state search issues *zero* per-call bank H2D
transfers.  The number of live rows rides into the kernel as a traced SMEM
scalar and the jitted search is keyed only on the padded buffer shapes,
which change exclusively at power-of-two capacity boundaries — thousands of
appends reuse one executable.  A host mirror is kept for snapshot/compact
and as the plain-numpy source of truth (`bank`, `alive()`).

Single-device search runs the fused Pallas topk_mips kernel.  On a mesh, the
bank rows shard across every device (logical axis "bank"); search is the
classic distributed-ANN reduction expressed in shard_map:

    local top-k per shard  →  all_gather(k·shards candidates)  →  re-rank

and the namespace mask rides along shard-local, so one sharded launch serves
a whole batch of tenants (see `sharded_topk(..., q_ns=, bank_ns=)`).

Exact search is the right call *because of the paper*: Advanced Augmentation
compresses raw dialogue into triples, keeping the bank orders of magnitude
smaller than chunk-RAG banks — small enough that exact MIPS at full HBM
bandwidth beats approximate pointer-chasing structures on TPU.

**Quantized dual-buffer residency** (`quantize="int8"`): the f32 host
mirror stays the bit-exact ground truth (snapshots, WAL replay and
compaction read it and are unchanged), while the DEVICE buffers become an
int8 code bank plus per-row f32 scales — ~4x less HBM footprint and ~4x
less bank bandwidth per search, scanned by the fused dequant+MIPS kernel
(kernels/topk_mips.py, `scales=`).  Appends quantize the new rows on the
host (symmetric per-row: scale = max|row|/127) and ride the same donated
in-place pow2 update path, so the zero-recompile / zero-bank-upload steady
state is preserved.  Every search over-fetches `rescore`x the requested k
from the quantized bank, then an exact f32 **rescore** (one host gather of
the candidate rows from the mirror + one small batched matmul) re-ranks
the candidates, so the returned scores are exact and recall@k against the
f32 oracle stays >= 0.95 (asserted in tests and CI).

**Tiered residency** (`demote_rows` / `promote_rows`): a row can be
resident (searchable on device) or demoted (device slot zeroed/label -1,
full-precision truth still in the host mirror — the "warm" tier).  The
store/lifecycle TierManager (core/tiering.py) demotes cold namespaces'
rows and promotes them back in batched pow2 uploads; `search_host` is the
transparent host-side fallback for queries that hit a demoted namespace.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.utils import next_pow2 as _next_pow2
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels import topk_mips as _tm
from repro.obs.telemetry import get_telemetry


# ---------------------------------------------------------------------------
# Device-side primitives.  All donate their buffer arguments so XLA updates
# the capacity-padded arrays in place (no realloc, no host round-trip); the
# jit cache is keyed on (capacity, update width) only, and callers pad the
# update width to a power of two (zero rows / -1 labels — exactly the
# unfilled-slot representation), so a lifecycle flusher draining a different
# number of sessions every interval still reuses a bounded executable set.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0, 1))
def _dev_append(bank, labels, vecs, ns, start):
    """Write `vecs` rows + `ns` labels at [start, start+m) in place."""
    bank = jax.lax.dynamic_update_slice(bank, vecs, (start, 0))
    labels = jax.lax.dynamic_update_slice(labels, ns, (start,))
    return bank, labels


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _dev_delete(bank, labels, ids):
    """Tombstone rows in place: zero the vectors, set the labels to -1."""
    bank = bank.at[ids].set(0.0)
    labels = labels.at[ids].set(-1)
    return bank, labels


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _dev_compact(bank, labels, gather, n_new):
    """Repack live rows in place: new row r takes old row `gather[r]` for
    r < n_new; the tail is zeroed / labeled -1.  Device-side compaction —
    no host round-trip, and the buffers keep their capacity, so the search
    executable (keyed on capacity) survives a compaction untouched."""
    live = jnp.arange(bank.shape[0]) < n_new
    bank = jnp.where(live[:, None], bank[gather], 0.0)
    labels = jnp.where(live, labels[gather], -1)
    return bank, labels


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _dev_restore(bank, labels, ids, vecs, ns):
    """Scatter rows + labels back into their slots (tier promotion: the
    demoted rows return from the host mirror).  Duplicate ids scatter the
    same values — pow2 id padding is idempotent."""
    bank = bank.at[ids].set(vecs)
    labels = labels.at[ids].set(ns)
    return bank, labels


# -- quantized variants: int8 code bank + (capacity,) f32 per-row scales ----

@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _dev_append_q(bank, scales, labels, vecs_i8, sc, ns, start):
    bank = jax.lax.dynamic_update_slice(bank, vecs_i8, (start, 0))
    scales = jax.lax.dynamic_update_slice(scales, sc, (start,))
    labels = jax.lax.dynamic_update_slice(labels, ns, (start,))
    return bank, scales, labels


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _dev_delete_q(bank, scales, labels, ids):
    bank = bank.at[ids].set(0)
    scales = scales.at[ids].set(0.0)
    labels = labels.at[ids].set(-1)
    return bank, scales, labels


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _dev_compact_q(bank, scales, labels, gather, n_new):
    live = jnp.arange(bank.shape[0]) < n_new
    bank = jnp.where(live[:, None], bank[gather], 0)
    scales = jnp.where(live, scales[gather], 0.0)
    labels = jnp.where(live, labels[gather], -1)
    return bank, scales, labels


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _dev_restore_q(bank, scales, labels, ids, vecs_i8, sc, ns):
    bank = bank.at[ids].set(vecs_i8)
    scales = scales.at[ids].set(sc)
    labels = labels.at[ids].set(ns)
    return bank, scales, labels


def quantize_rows_np(vecs: np.ndarray):
    """Symmetric per-row int8 quantization on the host (append/promote-time;
    rows are few, the bank-wide pass happens once per materialization).
    Matches `kernels/ref.quantize_rows_ref` bit-exactly: scale =
    max|row|/127, codes = round-half-even(row/scale) in [-127, 127]; an
    all-zero row keeps scale 0 and zero codes."""
    vecs = np.asarray(vecs, np.float32)
    amax = np.max(np.abs(vecs), axis=1) if vecs.size else \
        np.zeros((vecs.shape[0],), np.float32)
    scale = (amax / np.float32(127.0)).astype(np.float32)
    inv = np.where(scale > 0, np.float32(1.0) /
                   np.where(scale > 0, scale, 1), 0).astype(np.float32)
    codes = np.clip(np.rint(vecs * inv[:, None]), -127, 127).astype(np.int8)
    return codes, scale


@functools.partial(jax.jit,
                   static_argnames=("k", "use_kernel", "interpret", "uniform"))
def _search_device(bank, labels, queries, q_ns, n_valid, scanned, *, k: int,
                   use_kernel: bool, interpret: bool, uniform: bool):
    """The stable-shape jitted hot path: one masked top-k over the padded
    device bank.  `n_valid` is traced — appends within a capacity bucket
    reuse this executable.  With `uniform=True` the namespace structure is
    collapsed (any live row matches: the single-tenant / tombstone-only
    search).  Empty slots come back as (-inf, -1).  `scanned` is the
    device-side running count of the bank blocks the kernel scanned; it
    comes back with this launch's blocks added."""
    bank_ns = jnp.where(labels >= 0, 0, -1) if uniform else labels
    if use_kernel:
        s, i, n = _tm.topk_mips_counted(queries, bank, k, n_valid=n_valid,
                                        q_ns=q_ns, bank_ns=bank_ns,
                                        interpret=interpret)
        scanned = scanned + n
    else:
        s, i = kref.topk_mips_masked_ref(queries, bank, q_ns, bank_ns, k=k,
                                         n_valid=n_valid)
    return jnp.where(i >= 0, s, -jnp.inf), i, scanned


@functools.partial(jax.jit,
                   static_argnames=("k", "use_kernel", "interpret", "uniform"))
def _search_device_quant(bank_i8, scales, labels, queries, q_ns, n_valid,
                         scanned, *, k: int, use_kernel: bool,
                         interpret: bool, uniform: bool):
    """Quantized twin of `_search_device`: one fused dequant+MIPS launch
    over the int8 code bank (the bank scan reads 1 byte/element).  Same
    traced-`n_valid` stable-shape contract and `scanned` count; empty
    slots are (-inf, -1)."""
    bank_ns = jnp.where(labels >= 0, 0, -1) if uniform else labels
    if use_kernel:
        s, i, n = _tm.topk_mips_counted(queries, bank_i8, k,
                                        n_valid=n_valid, q_ns=q_ns,
                                        bank_ns=bank_ns, scales=scales,
                                        interpret=interpret)
        scanned = scanned + n
    else:
        s, i = kref.topk_mips_quant_masked_ref(queries, bank_i8, scales,
                                               q_ns, bank_ns, k=k,
                                               n_valid=n_valid)
    return jnp.where(i >= 0, s, -jnp.inf), i, scanned


@functools.partial(jax.jit, static_argnames=("k",))
def _rescore_exact(queries, cand_rows, cand_ids, *, k: int):
    """Exact f32 re-rank of the quantized candidates: `cand_rows`
    (Q, C, D) are the candidates' FULL-PRECISION rows gathered from the
    host mirror (the ground truth), `cand_ids` (Q, C) their bank ids (-1 =
    empty slot).  One small batched matmul; returns the top-k by exact
    score, (-inf, -1) padded — so the scores leaving a quantized index are
    exact, and quantization error only costs recall when a true top-k row
    falls outside the C-candidate pool."""
    s = jnp.einsum("qd,qcd->qc", queries, cand_rows,
                   precision=jax.lax.Precision.HIGHEST)
    s = jnp.where(cand_ids >= 0, s, _tm.NEG_INF)
    top_s, pos = jax.lax.top_k(s, k)
    top_i = jnp.take_along_axis(cand_ids, pos, axis=1)
    top_i = jnp.where(top_s > _tm.NEG_INF / 2, top_i, -1)
    return jnp.where(top_i >= 0, top_s, -jnp.inf), top_i


_I32_MAX = 2 ** 31 - 1


def _next_capacity(n: int, floor: int = 64) -> int:
    return max(floor, _next_pow2(n))


class VectorIndex:
    def __init__(self, dim: int, capacity: int = 1024, use_kernel: bool = True,
                 quantize: str = "none", rescore: int = 4):
        if quantize not in ("none", "int8"):
            raise ValueError(f"quantize {quantize!r} must be 'none' or "
                             "'int8'")
        if rescore < 1:
            raise ValueError("rescore must be >= 1")
        self.dim = dim
        self.n = 0
        self._n_dead = 0                 # O(1) tombstone counter
        self.use_kernel = use_kernel
        self.quantize = quantize
        self.rescore = rescore           # candidate over-fetch multiplier
        capacity = _next_capacity(capacity)
        # host mirror: source of truth for snapshot/compact and numpy readers
        self._bank = np.zeros((capacity, dim), np.float32)
        self._alive = np.ones((capacity,), bool)
        self._ns = np.zeros((capacity,), np.int32)   # raw per-row labels
        # tier residency: False = demoted (device slot dead, host truth
        # intact — the warm tier).  Searches only see resident rows.
        self._resident = np.ones((capacity,), bool)
        # device buffers (lazily materialized, then incrementally updated);
        # quantized mode keeps (capacity, dim) int8 codes + (capacity,) f32
        # scales instead of the (capacity, dim) f32 bank
        self._bank_dev = None
        self._labels_dev = None
        self._scales_dev = None
        # quantized-search observability: rescore_hits / rescore_rows is
        # the fraction of final top-k ids the quantized ordering already
        # had in ITS top-k (how often the rescore merely re-scores rather
        # than re-ranks) — exported as the "rescore hit rate" gauge.
        # blocks_total sums the (query tile, bank block) steps of every
        # kernel launch; the scanned ones are counted on the device
        self._counters = {"quant_searches": 0, "rescore_rows": 0,
                          "rescore_hits": 0, "blocks_total": 0}
        # (host base, device i32 or None): blocks_scanned = base + device.
        # Searches add to the device scalar without waiting on it; one
        # tuple swapped whole keeps a concurrent reader consistent.
        # `_unfolded` is the blocks_total of the launches the device
        # scalar holds, so it is folded into the base before it can wrap
        self._scanned = (0, None)
        self._unfolded = 0

    @property
    def counters(self) -> dict:
        """Search counters, with `blocks_scanned` read from the device
        (one scalar transfer): read them from stats(), not per search."""
        base, dev = self._scanned
        return dict(self._counters, blocks_scanned=base + (
            int(dev) if dev is not None else 0))

    # -- device residency ---------------------------------------------------
    @property
    def capacity(self) -> int:
        return self._bank.shape[0]

    def _effective_labels(self) -> np.ndarray:
        """(capacity,) i32: ns label for live rows in [0, n), else -1."""
        eff = np.full((self.capacity,), -1, np.int32)
        eff[: self.n] = np.where(self._alive[: self.n], self._ns[: self.n], -1)
        return eff

    def _invalidate_device(self) -> None:
        self._bank_dev = None
        self._labels_dev = None
        self._scales_dev = None

    def _ensure_device(self) -> None:
        """Materialize the device buffers from the host mirror.  Happens on
        the first search and after capacity changes (grow/compact/load) —
        never on the steady-state search path.  Demoted rows materialize
        with a -1 label (device-dead); quantized mode uploads int8 codes +
        scales instead of the f32 bank (~4x fewer bytes)."""
        if self._bank_dev is None:
            eff = np.where(self._resident, self._effective_labels(), -1)
            if self.quantize == "none":
                self._bank_dev = jnp.asarray(self._bank)
            else:
                codes, scales = quantize_rows_np(self._bank)
                self._bank_dev = jnp.asarray(codes)
                self._scales_dev = jnp.asarray(scales)
            self._labels_dev = jnp.asarray(eff)

    def row_labels_device(self):
        """(capacity,) i32 device array of effective namespace labels (live
        row -> its ns id, tombstone/unfilled/demoted -> -1).  Cached
        device-side and updated in place by add/delete; invalidated by
        compact/load_rows.  Returns the LIVE cached buffer — zero per-call
        device allocations (asserted in tests).  Callers must treat it as
        read-only and must not hold it across writes: the next add/delete
        donates (and on backends honoring donation, deletes) it."""
        self._ensure_device()
        return self._labels_dev

    # -- writes --------------------------------------------------------------
    def add(self, vecs, ns=None) -> np.ndarray:
        """Append rows.  `ns` labels the new rows' namespace (scalar or
        per-row sequence; default 0).  The device buffers are updated in
        place unless the append crosses a capacity boundary."""
        vecs = np.asarray(vecs, np.float32)
        if vecs.ndim == 1:
            vecs = vecs[None]
        m = vecs.shape[0]
        if np.ndim(ns) == 0:
            ns_rows = np.full((m,), 0 if ns is None else int(ns), np.int32)
        else:
            ns_rows = np.asarray(ns, np.int32)
            if ns_rows.shape != (m,):
                raise ValueError(
                    f"{ns_rows.shape[0]} namespace labels for {m} rows")
        if self.n + m > self.capacity:
            cap = _next_capacity(self.n + m, floor=2 * self.capacity)
            bank = np.zeros((cap, self.dim), np.float32)
            bank[: self.n] = self._bank[: self.n]
            alive = np.ones((cap,), bool)
            alive[: self.n] = self._alive[: self.n]
            labels = np.zeros((cap,), np.int32)
            labels[: self.n] = self._ns[: self.n]
            resident = np.ones((cap,), bool)
            resident[: self.n] = self._resident[: self.n]
            self._bank, self._alive, self._ns = bank, alive, labels
            self._resident = resident
            self._invalidate_device()     # re-upload once per doubling
        ids = np.arange(self.n, self.n + m)
        self._bank[self.n: self.n + m] = vecs
        self._alive[self.n: self.n + m] = True
        self._ns[self.n: self.n + m] = ns_rows
        self._resident[self.n: self.n + m] = True
        if self._bank_dev is not None:
            # pad the update width to the next power of two (bounded by the
            # remaining capacity) so variable-size flush batches reuse a
            # bounded set of append executables; pad rows are written as
            # zero vectors with -1 labels — the unfilled-slot representation
            # those slots already hold
            m_pad = max(m, min(_next_pow2(m), self.capacity - self.n))
            vec_up, ns_up = vecs, ns_rows
            if m_pad > m:
                vec_up = np.zeros((m_pad, self.dim), np.float32)
                vec_up[:m] = vecs
                ns_up = np.full((m_pad,), -1, np.int32)
                ns_up[:m] = ns_rows
            if self.quantize == "none":
                self._bank_dev, self._labels_dev = _dev_append(
                    self._bank_dev, self._labels_dev, jnp.asarray(vec_up),
                    jnp.asarray(ns_up), jnp.int32(self.n))
            else:
                # quantize the (few) new rows on the host; the bank-wide
                # int8 buffer is only ever touched in place
                codes, scales = quantize_rows_np(vec_up)
                self._bank_dev, self._scales_dev, self._labels_dev = \
                    _dev_append_q(self._bank_dev, self._scales_dev,
                                  self._labels_dev, jnp.asarray(codes),
                                  jnp.asarray(scales), jnp.asarray(ns_up),
                                  jnp.int32(self.n))
        self.n += m
        return ids

    @property
    def bank(self) -> np.ndarray:
        return self._bank[: self.n]

    @property
    def n_alive(self) -> int:
        return self.n - self._n_dead

    @property
    def n_dead(self) -> int:
        """Tombstone count, O(1) — cheap enough for the lifecycle daemon to
        poll every tick."""
        return self._n_dead

    def alive(self, ids=None):
        """Liveness of `ids` (or the full (n,) mask when ids is None)."""
        if ids is None:
            return self._alive[: self.n].copy()
        return self._alive[np.asarray(ids, np.int64)]

    def row_namespaces(self) -> np.ndarray:
        """(n,) i32 raw namespace labels (host mirror; tombstones keep their
        retired label here — the *effective* device labels mask them)."""
        return self._ns[: self.n].copy()

    def delete(self, ids) -> int:
        """Tombstone rows: ids keep their slots (the tid==row alignment with
        TripleStore/BM25 survives) but the vectors are physically zeroed and
        the rows never surface from search again.  Returns #newly deleted."""
        ids = np.asarray(ids, np.int64).ravel()
        ids = ids[(ids >= 0) & (ids < self.n)]
        ids = ids[self._alive[ids]]
        self._alive[ids] = False
        self._bank[ids] = 0.0
        self._n_dead += int(ids.size)
        if ids.size and self._bank_dev is not None:
            # pad the id width to a power of two (duplicate scatter of the
            # last id is idempotent) — bounded executable count under
            # variable-size evictions
            pad = _next_pow2(int(ids.size))
            ids_up = ids if pad == ids.size else np.concatenate(
                [ids, np.full((pad - ids.size,), ids[-1], np.int64)])
            if self.quantize == "none":
                self._bank_dev, self._labels_dev = _dev_delete(
                    self._bank_dev, self._labels_dev, jnp.asarray(ids_up))
            else:
                self._bank_dev, self._scales_dev, self._labels_dev = \
                    _dev_delete_q(self._bank_dev, self._scales_dev,
                                  self._labels_dev, jnp.asarray(ids_up))
        return int(ids.size)

    def compact(self) -> np.ndarray:
        """Physically drop tombstoned rows, repacking the bank.  Returns the
        old→new row id mapping as an (n_old,) int64 array (-1 for dropped
        rows); kept rows keep their relative order.  Callers owning
        row-aligned side tables (see core/store.py) must remap them with the
        returned array.

        Capacity is sticky: the buffers are NOT shrunk, and the device
        copies are repacked in place by a donated gather (`_dev_compact`) —
        a compaction moves zero bank bytes host->device and leaves the
        search executable (keyed on capacity) untouched."""
        n_old = self.n
        alive = self._alive[:n_old]
        old_to_new = np.full((n_old,), -1, np.int64)
        keep = np.where(alive)[0]
        old_to_new[keep] = np.arange(keep.size)
        n_new = int(keep.size)
        cap = self.capacity
        bank = np.zeros((cap, self.dim), np.float32)
        bank[:n_new] = self._bank[keep]
        labels = np.zeros((cap,), np.int32)
        labels[:n_new] = self._ns[keep]
        resident = np.ones((cap,), bool)
        resident[:n_new] = self._resident[keep]     # demoted rows stay warm
        self._bank = bank
        self._alive = np.ones((cap,), bool)
        self._ns = labels
        self._resident = resident
        self.n = n_new
        self._n_dead = 0
        if self._bank_dev is not None:
            gather = np.zeros((cap,), np.int32)
            gather[:n_new] = keep
            # the device gather carries demoted slots along as they are
            # (zeroed codes, -1 labels) — tier state survives a compaction
            if self.quantize == "none":
                self._bank_dev, self._labels_dev = _dev_compact(
                    self._bank_dev, self._labels_dev, jnp.asarray(gather),
                    jnp.int32(n_new))
            else:
                self._bank_dev, self._scales_dev, self._labels_dev = \
                    _dev_compact_q(self._bank_dev, self._scales_dev,
                                   self._labels_dev, jnp.asarray(gather),
                                   jnp.int32(n_new))
        return old_to_new

    def load_rows(self, bank, alive, ns=None) -> None:
        """Bulk-load a snapshot's rows (replaces any current content).
        `ns` carries the per-row namespace labels (default 0)."""
        bank = np.asarray(bank, np.float32)
        n = bank.shape[0]
        if bank.ndim != 2 or bank.shape[1] != self.dim:
            raise ValueError(f"bank shape {bank.shape} != (*, {self.dim})")
        cap = _next_capacity(n)
        self._bank = np.zeros((cap, self.dim), np.float32)
        self._bank[:n] = bank
        self._alive = np.ones((cap,), bool)
        self._alive[:n] = np.asarray(alive, bool)
        self._ns = np.zeros((cap,), np.int32)
        if ns is not None:
            self._ns[:n] = np.asarray(ns, np.int32)
        self._resident = np.ones((cap,), bool)   # a fresh load is all-hot
        self.n = n
        self._n_dead = n - int(self._alive[:n].sum())
        self._invalidate_device()

    # -- tiered residency (hot device rows / warm host rows) ------------------
    @property
    def n_resident(self) -> int:
        """Live rows currently searchable on device (the hot tier)."""
        m = self.n
        return int((self._alive[:m] & self._resident[:m]).sum())

    @property
    def n_warm(self) -> int:
        """Live rows demoted to the host mirror (the warm tier)."""
        m = self.n
        return int((self._alive[:m] & ~self._resident[:m]).sum())

    def resident_mask(self) -> np.ndarray:
        """(n,) bool: True where the row is device-resident."""
        return self._resident[: self.n].copy()

    def rows_in_namespace(self, ns_id: int) -> np.ndarray:
        """Live global row ids labeled `ns_id` (host mirror scan)."""
        m = self.n
        return np.where(self._alive[:m] & (self._ns[:m] == ns_id))[0]

    def demote_rows(self, ids) -> int:
        """Move rows to the warm tier: their DEVICE slots are zeroed and
        label -1 (they stop matching any query), while the host mirror — the
        full-precision ground truth — is untouched, so snapshots, WAL
        replay, compaction and `promote_rows` all still see them.  In-place
        donated scatter, pow2-padded: no recompile churn, no bank upload.
        Returns #rows newly demoted."""
        ids = np.asarray(ids, np.int64).ravel()
        ids = ids[(ids >= 0) & (ids < self.n)]
        ids = ids[self._resident[ids]]
        if not ids.size:
            return 0
        self._resident[ids] = False
        if self._bank_dev is not None:
            pad = _next_pow2(int(ids.size))
            ids_up = ids if pad == ids.size else np.concatenate(
                [ids, np.full((pad - ids.size,), ids[-1], np.int64)])
            if self.quantize == "none":
                self._bank_dev, self._labels_dev = _dev_delete(
                    self._bank_dev, self._labels_dev, jnp.asarray(ids_up))
            else:
                self._bank_dev, self._scales_dev, self._labels_dev = \
                    _dev_delete_q(self._bank_dev, self._scales_dev,
                                  self._labels_dev, jnp.asarray(ids_up))
        return int(ids.size)

    def promote_rows(self, ids) -> int:
        """Bring warm rows back to the device: one batched pow2-padded
        in-place scatter of the rows (quantized on the host first in int8
        mode) plus their effective labels, from the host mirror.  Returns
        #rows promoted."""
        ids = np.asarray(ids, np.int64).ravel()
        ids = ids[(ids >= 0) & (ids < self.n)]
        ids = ids[~self._resident[ids]]
        if not ids.size:
            return 0
        self._resident[ids] = True
        if self._bank_dev is not None:
            pad = _next_pow2(int(ids.size))
            ids_up = ids if pad == ids.size else np.concatenate(
                [ids, np.full((pad - ids.size,), ids[-1], np.int64)])
            vecs = self._bank[ids_up]
            # tombstoned-while-warm rows come back as device tombstones
            ns_up = np.where(self._alive[ids_up], self._ns[ids_up],
                             -1).astype(np.int32)
            if self.quantize == "none":
                self._bank_dev, self._labels_dev = _dev_restore(
                    self._bank_dev, self._labels_dev, jnp.asarray(ids_up),
                    jnp.asarray(vecs), jnp.asarray(ns_up))
            else:
                codes, scales = quantize_rows_np(vecs)
                self._bank_dev, self._scales_dev, self._labels_dev = \
                    _dev_restore_q(self._bank_dev, self._scales_dev,
                                   self._labels_dev, jnp.asarray(ids_up),
                                   jnp.asarray(codes), jnp.asarray(scales),
                                   jnp.asarray(ns_up))
        return int(ids.size)

    def search_host(self, queries, q_ns, k: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side masked exact search over the FULL host mirror (hot and
        warm rows alike) — the transparent fallback for queries whose
        namespace is demoted from the device bank.  Pure numpy: exact f32
        scores, same (-inf, -1) fill contract as the device searches."""
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        Q = queries.shape[0]
        if self.n == 0 or self.n_alive == 0:
            return self._empty(Q, k)
        m = self.n
        eff = np.where(self._alive[:m], self._ns[:m], -1)
        s = queries @ self._bank[:m].T                      # (Q, n)
        ok = np.asarray(q_ns, np.int32)[:, None] == eff[None, :]
        s = np.where(ok, s, -np.inf)
        kk = min(k, m)
        part = np.argpartition(-s, kk - 1, axis=1)[:, :kk]
        ps = np.take_along_axis(s, part, axis=1)
        order = np.argsort(-ps, axis=1, kind="stable")
        idx = np.take_along_axis(part, order, axis=1).astype(np.int64)
        scs = np.take_along_axis(ps, order, axis=1).astype(np.float32)
        idx = np.where(np.isfinite(scs), idx, -1)
        if kk < k:
            scs = np.pad(scs, ((0, 0), (0, k - kk)),
                         constant_values=-np.inf)
            idx = np.pad(idx, ((0, 0), (0, k - kk)), constant_values=-1)
        return scs, idx

    # -- reads ---------------------------------------------------------------
    def _empty(self, Q: int, k: int) -> Tuple[np.ndarray, np.ndarray]:
        return (np.full((Q, k), -np.inf, np.float32),
                np.full((Q, k), -1, np.int64))

    def _run_search(self, queries, q_ns, k: int, labels=None,
                    uniform: bool = False):
        """Shared driver for every search flavor: clamp k to the padded
        capacity, run the stable-shape jitted search, hand back device
        arrays.  `labels=None` uses the cached device labels.

        Quantized mode over-fetches `rescore`x k candidates from the int8
        bank (candidate count bucketed to pow2 — one executable per (Q, k)
        bucket), then re-ranks them by exact f32 score: one host gather of
        the candidate rows from the mirror + one small batched matmul
        (`_rescore_exact`).  The gather moves Q*C*D*4 bytes — candidates,
        never the bank."""
        self._ensure_device()
        if labels is None:
            labels = self._labels_dev
        kk = min(k, self.capacity)
        scanned = self._count_launch(queries.shape[0])
        if self.quantize == "none":
            s, i, scanned = _search_device(
                self._bank_dev, labels, queries, q_ns, jnp.int32(self.n),
                scanned, k=kk, use_kernel=self.use_kernel,
                interpret=kops._interpret_default(), uniform=uniform)
            self._scanned = (self._scanned[0], scanned)
            return s, i, kk
        kc = min(self.capacity, _next_pow2(kk * self.rescore))
        s, i, scanned = _search_device_quant(
            self._bank_dev, self._scales_dev, labels, queries, q_ns,
            jnp.int32(self.n), scanned, k=kc, use_kernel=self.use_kernel,
            interpret=kops._interpret_default(), uniform=uniform)
        self._scanned = (self._scanned[0], scanned)
        tel = get_telemetry()
        # the rescore's host work; its two device reads are timed apart
        with tel.span("dense.rescore"):
            with tel.span("device.wait"):
                i_host = np.asarray(i)               # (Q, C) candidate ids
            cand = self._bank[np.clip(i_host, 0, self.capacity - 1)]
            s, i = _rescore_exact(queries, jnp.asarray(cand),
                                  jnp.asarray(i_host), k=kk)
            self._counters["quant_searches"] += 1
            with tel.span("device.wait"):
                i_np = np.asarray(i)                 # small (Q, k) D2H
            firstk = i_host[:, :kk]
            for r in range(i_np.shape[0]):
                fin = i_np[r][i_np[r] >= 0]
                self._counters["rescore_rows"] += int(fin.size)
                self._counters["rescore_hits"] += int(np.isin(
                    fin, firstk[r]).sum())
        return s, i, kk

    def _count_launch(self, Q: int):
        """Count one launch's blocks on the host; return the device
        scalar its scanned blocks are added to.  The device scalar is
        read into the host base only once per 2^31 counted blocks."""
        total = _tm.grid_blocks(Q, self.capacity) if self.use_kernel else 0
        base, dev = self._scanned
        if dev is None or self._unfolded + total > _I32_MAX:
            self._scanned = (base + (int(dev) if dev is not None else 0),
                             None)
            self._unfolded = 0
            dev = jnp.zeros((), jnp.int32)
        self._counters["blocks_total"] += total
        self._unfolded += total
        return dev

    def _to_host(self, s, i, k: int, kk: int):
        s = np.asarray(s)
        i = np.asarray(i, np.int64)
        if kk < k:
            s = np.pad(s, ((0, 0), (0, k - kk)), constant_values=-np.inf)
            i = np.pad(i, ((0, 0), (0, k - kk)), constant_values=-1)
        return s, i

    def search(self, queries, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """queries (Q, D) -> (scores (Q, k), ids (Q, k)); empty slots (rows
        beyond n, tombstones crowding out candidates) are (-inf, -1).  Runs
        the namespace-collapsed masked search over the device-resident bank:
        k stays static across add()/delete() — no retrace, no over-fetch."""
        queries = jnp.asarray(queries, jnp.float32)
        if queries.ndim == 1:
            queries = queries[None]
        Q = queries.shape[0]
        if self.n == 0 or self.n_alive == 0:
            return self._empty(Q, k)
        s, i, kk = self._run_search(
            queries, jnp.zeros((Q,), jnp.int32), k, uniform=True)
        return self._to_host(s, i, k, kk)

    def search_batch(self, queries, q_ns, k: int):
        """The multi-tenant hot path: one stable-shape launch over the
        device-resident bank using the *cached* device labels (no per-call
        label rebuild, no bank transfer).  Returns DEVICE arrays
        (scores (Q, k) f32, ids (Q, k) i32) so callers can keep fusing
        on-device; empty slots are (-inf, -1)."""
        queries = jnp.asarray(queries, jnp.float32)
        if queries.ndim == 1:
            queries = queries[None]
        Q = queries.shape[0]
        if self.n == 0 or self.n_alive == 0:
            return (jnp.full((Q, k), -jnp.inf, jnp.float32),
                    jnp.full((Q, k), -1, jnp.int32))
        q_ns = jnp.asarray(q_ns, jnp.int32)
        s, i, kk = self._run_search(queries, q_ns, k)
        if kk < k:
            s = jnp.pad(s, ((0, 0), (0, k - kk)), constant_values=-jnp.inf)
            i = jnp.pad(i, ((0, 0), (0, k - kk)), constant_values=-1)
        return s, i

    def search_masked(self, queries, q_ns, row_ns, k: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched multi-tenant search with *caller-supplied* labels:
        q_ns (Q,) >= 0 is each query's namespace, row_ns (n,) labels every
        bank row; tombstoned rows are masked regardless of their label.
        The bank itself stays device-resident; only the (n,) label vector is
        uploaded.  Prefer `search_batch` (cached labels) on the hot path."""
        queries = jnp.asarray(queries, jnp.float32)
        if queries.ndim == 1:
            queries = queries[None]
        Q = queries.shape[0]
        if self.n == 0 or self.n_alive == 0:
            return self._empty(Q, k)
        row_ns = np.asarray(row_ns, np.int32)
        if row_ns.shape != (self.n,):
            raise ValueError(f"row_ns shape {row_ns.shape} != ({self.n},)")
        eff = np.full((self.capacity,), -1, np.int32)
        ok = self._alive[: self.n] & self._resident[: self.n]
        eff[: self.n] = np.where(ok, row_ns, -1)
        s, i, kk = self._run_search(queries, jnp.asarray(q_ns, jnp.int32), k,
                                    labels=jnp.asarray(eff))
        return self._to_host(s, i, k, kk)


# ---------------------------------------------------------------------------
# Distributed search (shard_map): used by launch/dryrun and on real meshes.
# ---------------------------------------------------------------------------

def sharded_topk(queries, bank, k: int, mesh: Mesh, axis_names=("data", "model"),
                 *, q_ns=None, bank_ns=None, use_kernel: bool = True,
                 interpret: Optional[bool] = None):
    """bank rows sharded over `axis_names` (flattened); returns global
    (scores (Q,k), ids (Q,k)).  Local top-k → all_gather → re-rank.

    Local shard scoring runs the fused Pallas kernel (interpret mode
    off-TPU); pass `use_kernel=False` for the pure-jnp oracle path.

    Namespace-masked sharded search: pass q_ns (Q,) i32 and bank_ns (N,)
    i32 (both or neither; bank_ns shards with the bank rows, -1 marks
    tombstones).  Cross-namespace rows never surface — results match the
    single-device masked search exactly (ids -1 / scores NEG_INF for
    unfilled slots), including when a tenant owns fewer than k rows or
    k exceeds the per-shard row count."""
    flat_axes = tuple(a for a in axis_names if a in mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in flat_axes]))
    N = bank.shape[0]
    assert N % n_shards == 0, (N, n_shards)
    shard_rows = N // n_shards
    masked = q_ns is not None or bank_ns is not None
    if masked:
        assert q_ns is not None and bank_ns is not None, \
            "q_ns and bank_ns must be given together"
        q_ns = jnp.asarray(q_ns, jnp.int32)
        bank_ns = jnp.asarray(bank_ns, jnp.int32)
    interp = kops._interpret_default() if interpret is None else interpret
    k_local = min(k, shard_rows)

    def _rerank(s, i):
        # gather candidates from every shard, then re-rank globally
        s_all = jax.lax.all_gather(s, flat_axes, axis=1, tiled=True)
        i_all = jax.lax.all_gather(i, flat_axes, axis=1, tiled=True)
        top_s, pos = jax.lax.top_k(s_all, k)
        top_i = jnp.take_along_axis(i_all, pos, axis=1)
        return top_s, top_i

    def local(q, b):
        # positional index of this shard along the flattened bank axes
        idx = jax.lax.axis_index(flat_axes)
        if use_kernel:
            s, i = _tm.topk_mips(q, b, k_local, interpret=interp)
        else:
            s, i = kref.topk_mips_ref(q, b, k=k_local)
        i = i + idx * shard_rows
        return _rerank(s, i)

    def local_masked(q, b, qns, bns):
        idx = jax.lax.axis_index(flat_axes)
        if use_kernel:
            s, i = _tm.topk_mips(q, b, k_local, q_ns=qns, bank_ns=bns,
                                 interpret=interp)
        else:
            s, i = kref.topk_mips_masked_ref(q, b, qns, bns, k=k_local)
        # -1 sentinels (masked-out slots) must not be offset into real ids
        i = jnp.where(i >= 0, i + idx * shard_rows, -1)
        top_s, top_i = _rerank(s, i)
        return top_s, jnp.where(top_s > _tm.NEG_INF / 2, top_i, -1)

    spec_bank = P(flat_axes)
    # outputs are replicated by construction (all_gather + local re-rank);
    # the replication checker can't prove it, so we assert it ourselves
    if masked:
        fn = jax.shard_map(local_masked, mesh=mesh,
                           in_specs=(P(), spec_bank, P(), spec_bank),
                           out_specs=(P(), P()), check_vma=False)
        return fn(queries, bank, q_ns, bank_ns)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(), spec_bank),
                       out_specs=(P(), P()), check_vma=False)
    return fn(queries, bank)
