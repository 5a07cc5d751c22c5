"""Compile the served path's device programs for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for a v5e:2x2
topology that is only described, so what the chip's compiler would refuse
(an unaligned slice, too much VMEM, a program over HBM) fails here first.
Every kernel program must hold the Mosaic kernel (`tpu_custom_call`).
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture — never at import time:
only one process may load the TPU library, and every test worker imports
every test file.  The persistent compilation cache stays off around these
compiles (an entry written for a described chip cannot be read back)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.graph import _expand_device
from repro.core.hybrid import _rrf_fuse_device
from repro.core.vector_index import (_search_device, _search_device_quant,
                                     sharded_topk)
from repro.kernels import topk_mips as tm

BANK_ROWS, D, Q, K = 1 << 20, 256, 8, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_topk_mips_compiles_at_bank_scale(one_chip, masked, quantized):
    """The four kernel variants over a 2^20 x 256 bank, Q=8, k=64."""
    args = [_spec((Q, D), jnp.float32, one_chip),
            _spec((BANK_ROWS, D), jnp.int8 if quantized else jnp.float32,
                  one_chip),
            _spec((), jnp.int32, one_chip)]
    if quantized:
        args.append(_spec((BANK_ROWS,), jnp.float32, one_chip))
    if masked:
        args += [_spec((Q,), jnp.int32, one_chip),
                 _spec((BANK_ROWS,), jnp.int32, one_chip)]

    def search(q, bank, n_valid, *rest):
        scales = rest[0] if quantized else None
        q_ns, bank_ns = rest[-2:] if masked else (None, None)
        return tm.topk_mips(q, bank, K, n_valid=n_valid, q_ns=q_ns,
                            bank_ns=bank_ns, scales=scales, interpret=False)

    compiled = jax.jit(search).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    bank_bytes = BANK_ROWS * D * (1 if quantized else 4)
    assert mem.argument_size_in_bytes >= bank_bytes


@pytest.mark.parametrize("q", [8, 64])
@pytest.mark.parametrize("quantized", [False, True])
def test_served_search_compiles_with_block_skip(one_chip, quantized, q):
    """The jitted searches the dense stage launches, at 2^20 x 256 with
    the served k (64 f32, 256 = 4x rescore over-fetch int8): the block
    flags' pre-pass and the scalar-prefetch masked kernel.  The kernel op
    keeps the search's name, which the benchmark's trace reduction finds,
    and the pre-pass adds no bank-sized temporary."""
    i32 = jnp.int32
    bank = _spec((BANK_ROWS, D), jnp.int8 if quantized else jnp.float32,
                 one_chip)
    rest = (_spec((BANK_ROWS,), i32, one_chip), _spec((q, D), jnp.float32,
                                                      one_chip),
            _spec((q,), i32, one_chip), _spec((), i32, one_chip),
            _spec((), i32, one_chip))
    if quantized:
        scales = _spec((BANK_ROWS,), jnp.float32, one_chip)
        lowered = _search_device_quant.lower(
            bank, scales, *rest, k=4 * K, use_kernel=True, interpret=False,
            uniform=False)
    else:
        lowered = _search_device.lower(bank, *rest, k=K, use_kernel=True,
                                       interpret=False, uniform=False)
    compiled = lowered.compile()
    name = "_search_device_quant" if quantized else "_search_device"
    assert any(line.lstrip().startswith(f"%{name}.")
               for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line)
    mem = compiled.memory_analysis()
    if mem is not None:
        assert mem.temp_size_in_bytes < 8 * 2 ** 20


def test_sharded_topk_masked_compiles_on_four_chips(topo):
    """Namespace-masked sharded search, 2^20 rows per chip on a 2x2 mesh:
    each chip holds its own quarter and runs the kernel on it."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(("data", "model")))
    n = 4 * BANK_ROWS
    args = (_spec((Q, D), jnp.float32, rep), _spec((n, D), jnp.float32, rows),
            _spec((Q,), jnp.int32, rep), _spec((n,), jnp.int32, rows))

    def search(q, bank, q_ns, bank_ns):
        return sharded_topk(q, bank, K, mesh, q_ns=q_ns, bank_ns=bank_ns,
                            interpret=False)

    compiled = jax.jit(search).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text
    # each chip's arguments hold its quarter of the bank, not all of it
    mem = compiled.memory_analysis()
    assert BANK_ROWS * D * 4 <= mem.argument_size_in_bytes < n * D * 4


def test_rrf_fuse_batch_compiles_at_serving_width(one_chip):
    """Fusion of the dense, sparse and graph rankings of one tick."""
    P_ = 3 * K
    args = (_spec((Q, P_), jnp.int32, one_chip),
            _spec((P_,), jnp.int32, one_chip),
            _spec((P_,), jnp.int32, one_chip),
            _spec((Q, 3), jnp.float32, one_chip))
    compiled = _rrf_fuse_device.lower(*args, k=K, c=60.0).compile()
    assert compiled.memory_analysis() is not None


def test_graph_expand_compiles_at_serving_width(one_chip):
    """Two-hop expansion over the lanes of a 2^21-row bank (a 2^20-row
    fill plus the conversations written over it) with 2^22 edges."""
    rows, edges, nodes = 2 * BANK_ROWS, 4 * BANK_ROWS, 1 << 18
    i32 = jnp.int32
    lanes = (_spec((edges,), i32, one_chip), _spec((edges,), i32, one_chip),
             _spec((edges,), i32, one_chip),
             _spec((edges,), jnp.float32, one_chip),
             _spec((nodes,), i32, one_chip), _spec((rows,), i32, one_chip),
             _spec((rows,), i32, one_chip), _spec((rows,), i32, one_chip))
    rankings = (_spec((Q, K), i32, one_chip), _spec((Q, K), i32, one_chip))
    rest = (_spec((Q,), i32, one_chip), _spec((Q, 3), jnp.float32, one_chip),
            _spec((Q,), i32, one_chip), _spec((), i32, one_chip),
            _spec((), i32, one_chip))
    compiled = _expand_device.lower(*lanes, rankings, *rest, hops=2, k=K,
                                    seed_k=8, decay=0.5).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9
