"""Small shared helpers: pytree sizes, dtype plumbing, deterministic RNG."""
from __future__ import annotations

import dataclasses
import logging
import math
import os
import pathlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def tree_num_params(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_num_bytes(tree: PyTree) -> int:
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype) if hasattr(x, "astype") else x, tree)


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} EiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


class count_compiles:
    """Context manager counting XLA compilations inside the `with` block by
    capturing jax's `jax_log_compiles` log records.  The handle exposes
    `.count` and `.msgs`.  Used by the retrieval-engine tests and the
    steady-state benchmark to assert the device-resident search never
    recompiles while the bank grows within one capacity bucket — keep the
    'Compiling' message match in sync with the pinned jax version (the
    tests include a positive control so silent breakage is caught)."""

    class _Handler(logging.Handler):
        def __init__(self):
            super().__init__(level=logging.DEBUG)
            self.count, self.msgs = 0, []

        def emit(self, record):
            msg = record.getMessage()
            if "Compiling" in msg:
                self.count += 1
                self.msgs.append(msg[:120])

    def __enter__(self):
        self.handler = self._Handler()
        self.logger = logging.getLogger("jax")
        self.prev_level = self.logger.level
        self.logger.addHandler(self.handler)
        self.logger.setLevel(logging.DEBUG)
        jax.config.update("jax_log_compiles", True)
        return self.handler

    def __exit__(self, *exc):
        jax.config.update("jax_log_compiles", False)
        self.logger.removeHandler(self.handler)
        self.logger.setLevel(self.prev_level)
        return False


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def fold_key(key: jax.Array, *names: str) -> jax.Array:
    """Derive a named sub-key deterministically from string names."""
    for name in names:
        h = int.from_bytes(name.encode("utf-8")[:8].ljust(8, b"\0"), "little")
        key = jax.random.fold_in(key, h % (2**31 - 1))
    return key


def asdict_shallow(dc) -> dict:
    return {f.name: getattr(dc, f.name) for f in dataclasses.fields(dc)}


def stable_hash(text: str, mod: int) -> int:
    """Deterministic (cross-run, cross-process) string hash -> [0, mod)."""
    h = 2166136261
    for b in text.encode("utf-8"):
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h % mod


def log_bucket(x: float, buckets: int = 64) -> int:
    if x <= 0:
        return 0
    return min(buckets - 1, int(math.log2(x + 1)))


def init_compilation_cache() -> str:
    """Mount JAX's persistent compilation cache and return its directory.
    Where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself and this
    sets nothing; otherwise the cache lives at `.jax_cache/` in the repo
    root — a fixed path, because the path is part of what a later process
    must find again.  Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
