"""Sharding rules, collectives, and a real (reduced-device) dry-run."""

import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class FakeMesh:
    """Shape-only stand-in so rule tests don't touch jax devices."""

    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = FakeMesh(data=16, model=16)
POD = FakeMesh(pod=2, data=16, model=16)


def spec(axes, shape, kind="act", mesh=MESH):
    return shd._resolve(tuple(axes), tuple(shape), mesh,
                        shd.RULE_SETS["default"][0 if kind == "act" else 1])


# ------------------------------------------------------------------ rules
def test_batch_folds_over_pod_and_data():
    assert spec(("batch", "seq"), (256, 4096), mesh=POD) == \
        P(("pod", "data"), "model")


def test_heads_shard_when_divisible():
    s = spec(("batch", "seq", "heads", "head_dim"), (32, 4096, 48, 128))
    assert s == P("data", None, "model", None)


def test_seq_parallel_fallback_when_heads_dont_divide():
    """llama4: 40 heads % 16 != 0 -> seq takes the model axis."""
    s = spec(("batch", "seq", "heads", "head_dim"), (32, 4096, 40, 128))
    assert s == P("data", "model", None, None)


def test_kv_cache_seq_sharding_fallback():
    # starcoder2 decode: kv=4 can't shard -> kv_seq takes model
    s = spec(("batch", "kv_seq", "kvheads", "head_dim"),
             (128, 32768, 4, 128))
    assert s == P("data", "model", None, None)
    # qwen2moe: kv=16 shards -> kv_seq stays unsharded
    s = spec(("batch", "kv_seq", "kvheads", "head_dim"),
             (128, 32768, 16, 128))
    assert s == P("data", None, "model", None)


def test_expert_ep_full_sharding():
    """llama4 experts: (expert->model, ffn->data) — no FSDP dim left."""
    s = spec(("layers", "expert", "expert_out", "expert_in"),
             (24, 128, 8192, 5120), kind="param")
    assert s == P(None, "model", "data", None)


def test_expert_fallback_per_expert_tp():
    """qwen2-moe: 60 experts don't divide -> expert_out falls to model."""
    s = spec(("layers", "expert", "expert_out", "expert_in"),
             (24, 60, 1408, 2048), kind="param")
    assert s == P(None, None, "model", None)


def test_param_fsdp_embed_on_data():
    s = spec(("mlp", "embed"), (24576, 6144), kind="param")
    assert s == P("model", "data")


def test_param_specs_tree():
    cfg = configs.get_smoke("starcoder2_15b")
    from repro.models import transformer as T

    shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    specs = shd.param_specs(shapes, MESH)
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat) == len(jax.tree.leaves(shapes))


def test_constrain_noop_without_mesh():
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    assert shd.constrain(x, "batch", "embed") is x


# ------------------------------------------------------------ collectives
def test_int8_all_gather_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.distributed.collectives import int8_all_gather
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 6)) * 0.3
        spec = P("data", "model")
        xs = jax.device_put(x, NamedSharding(mesh, spec))
        def f(x):
            g = int8_all_gather(x, mesh, spec, axis="data")
            return g, jnp.sum(g * jnp.arange(48.0).reshape(8, 6))
        with jax.set_mesh(mesh):
            out = jax.jit(lambda x: f(x)[0])(xs)
            err = float(jnp.max(jnp.abs(out - x)))
            assert err <= float(jnp.max(jnp.abs(x))) / 127 + 1e-6, err
            gr = jax.jit(jax.grad(lambda x: f(x)[1]))(xs)
            assert bool(jnp.allclose(gr, jnp.arange(48.0).reshape(8, 6)))
            hlo = jax.jit(lambda x: f(x)[0]).lower(xs).compile().as_text()
            assert any("all-gather(" in l and "= s8" in l
                       for l in hlo.splitlines()), "no int8 wire format"
        print("INT8_AG_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": SRC})
    assert "INT8_AG_OK" in r.stdout, r.stdout + r.stderr


# ------------------------------------------------------------ dry-run
def test_dryrun_parse_collectives():
    sys.path.insert(0, SRC)
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(%p0), replica_groups=[2,4]<=[8]
  %ar.1 = f32[64]{0} all-reduce(%x), to_apply=%sum
  %a2a = f32[2,4,8]{2,1,0} all-to-all(%y), dimensions={0}
"""
    # the module sets XLA_FLAGS at import (its documented contract);
    # jax is already initialized here, so only the env var needs restoring
    jax.devices()
    prev = os.environ.get("XLA_FLAGS")
    try:
        from repro.launch import dryrun
    finally:
        if prev is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = prev

    c = dryrun.parse_collectives(hlo)
    assert c["all-gather"]["count"] == 1
    assert c["all-gather"]["bytes"] == 8 * 128 * 2
    assert c["all-reduce"]["bytes"] == 64 * 4
    assert c["all-to-all"]["count"] == 1


@pytest.mark.slow
def test_dryrun_single_cell_subprocess():
    """Full dry-run machinery on one real cell (the production 16x16 mesh
    with 512 forced host devices) — the same path --all uses."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch import dryrun
        res = dryrun.run_cell("gemma_2b", "decode_32k", multi_pod=False,
                              quant="msgemm", verbose=False)
        assert res["status"] == "ok", res
        assert res["memory"]["total_per_device_gb"] < 16.0
        print("DRYRUN_CELL_OK", res["memory"]["total_per_device_gb"])
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560,
                       env={**os.environ, "PYTHONPATH": SRC})
    assert "DRYRUN_CELL_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_all_dryrun_artifacts_ok():
    """Every recorded dry-run artifact is ok/skipped (none failed)."""
    import glob
    import json

    files = glob.glob(os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "results", "dryrun",
        "*.json"))
    if not files:
        pytest.skip("dry-run artifacts not generated")
    bad = []
    for f in files:
        with open(f) as fh:
            r = json.load(fh)
        if r["status"] == "failed":
            bad.append(r["cell"])
    assert not bad, bad
