"""Partitioning rules + distributed retrieval (subprocess with host devices,
so the main pytest process keeps its single CPU device)."""
import subprocess
import sys
import textwrap

import pytest

from repro.common import partitioning as pt
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.launch.mesh import make_host_mesh
from repro.models.model_api import Model


def test_spec_divisibility_guard_and_head_fallback():
    mesh = make_host_mesh(1, 1)   # sizes 1: everything trivially shards
    rules = pt.standard_rules(mesh)
    spec = rules.spec_for(("embed", "heads", "head_dim"), (100, 40, 128))
    assert len(spec) == 3


def test_param_specs_shardable_on_production_shape():
    """Every param of every arch must yield a valid PartitionSpec under the
    production axis sizes (divisibility checked arithmetically, no devices)."""
    import numpy as np

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    rules = pt.MeshRules(mesh=FakeMesh(), rules={
        "layers": None, "vocab": "model", "embed": None, "heads": "model",
        "kv_heads": "model", "head_dim": None, "ff": "model",
        "experts": "model", "expert_cap": "data", "batch": "data",
        "seq": None, "state": "model", "bank": ("data", "model"),
        "topk": None,
    })
    from repro.common.module import is_spec
    import jax
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        specs = Model(cfg).param_specs()
        leaves = [s for s in jax.tree.leaves(
            specs, is_leaf=is_spec) if is_spec(s)]
        for s in leaves:
            p = rules.spec_for(s.axes, s.shape)
            for dim, phys in zip(s.shape, tuple(p) + (None,) * len(s.shape)):
                if phys is None:
                    continue
                size = np.prod([rules.mesh.shape[a] for a in
                                (phys if isinstance(phys, tuple) else (phys,))])
                assert dim % size == 0, (arch, s.shape, p)


def test_meshes_have_auto_axes_and_peaks_are_keyed_by_device_kind():
    import jax
    from repro.launch import mesh as mesh_lib
    mesh = make_host_mesh(1, 1)
    assert set(mesh.axis_types) == {jax.sharding.AxisType.Auto}
    v5e = mesh_lib.chip_peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        mesh_lib.chip_peaks("cpu")


# (sharded_topk parity moved to tests/test_distributed_parity.py, which
# also covers the k > shard_rows edge and the Pallas-kernel comparison)


@pytest.mark.slow
def test_dryrun_smoke_subprocess():
    """A miniature dry-run on 8 host devices: lower+compile one reduced arch
    per family on a (4, 2) mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, jax
        from repro.configs import get_config
        from repro.launch.sharding import build_step
        from repro.launch.mesh import make_mesh
        from repro.models.config import INPUT_SHAPES
        mesh = make_mesh((4, 2), ("data", "model"))
        for arch in ("internlm2-1.8b", "mamba2-2.7b", "phi3.5-moe-42b-a6.6b"):
            cfg = get_config(arch).reduced()
            for sh_name, bat, sq in (("train_4k", 8, 64), ("decode_32k", 8, 64)):
                shape = dataclasses.replace(
                    INPUT_SHAPES[sh_name], global_batch=bat, seq_len=sq)
                with mesh:
                    b = build_step(cfg, shape, mesh)
                    c = b.fn.lower(*b.args).compile()
                    assert c.cost_analysis() is not None
        print("DRYRUN_SMOKE_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=560,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert "DRYRUN_SMOKE_OK" in out.stdout, out.stderr[-2000:]
