"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers one kernel at gemma-2b's real widths with
``interpret=False`` and compiles it for a v5e described by
``jax.experimental.topologies``, so a block that breaks the TPU tiling,
an op Mosaic cannot lower, or a VMEM request over the limit fails here on
the CPU instead of on the chip.  The topology is described inside a
module fixture (only the worker that runs this file loads the TPU
compiler), and the persistent compilation cache is off around the
compiles: an entry written for a described chip cannot be read back.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.epilogue import Epilogue
from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention_pallas

D, SB = 3, 36  # serve CLI default LUT depth and its scale block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("name,m,k,b,ep,fused", [
    ("up", 16384, 2048, 8, Epilogue(), True),
    ("gate", 16384, 2048, 8, Epilogue(act="gelu"), True),
    ("down", 2048, 16384, 8, Epilogue(residual=True), True),
    ("up-prefill", 16384, 2048, 64, Epilogue(), True),
    ("up-legacy", 16384, 2048, 8, Epilogue(), False),
    # wk/wv column-sharded over model=4: a sub-128 m, one m tile
    ("kv-shard", 64, 2048, 8, Epilogue(), True),
    ("kv-shard-legacy", 64, 2048, 8, Epilogue(), False),
])
def test_msgemm_compiles_for_v5e(one_chip, name, m, k, b, ep, fused):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def f(codes, x, sc, *res):
        return ops.msgemm(codes, x, D, scales=sc, scale_block=SB,
                          interpret=False, acc_in_vmem=fused, epilogue=ep,
                          residual=res[0] if res else None)

    args = [sds((m, k), jnp.uint8), sds((k, b), jnp.float32),
            sds((m, -(-k // SB)), jnp.float32)]
    if ep.residual:
        args.append(sds((m, b), jnp.float32))
    assert "tpu_custom_call" in _compile_text(f, *args)


@pytest.mark.parametrize("m,k,b", [(16384, 2048, 8), (2048, 16384, 64)])
def test_int4_matmul_compiles_for_v5e(one_chip, m, k, b):
    def f(u8, sc, x):
        return ops.int4_matmul(u8, sc, x, scale_block=SB, interpret=False)

    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((m, k // 2), jnp.uint8), ((m, -(-k // SB)), jnp.float32),
        ((k, b), jnp.float32))]
    assert "tpu_custom_call" in _compile_text(f, *shapes)


@pytest.mark.parametrize("C", [1, 64])
def test_paged_attention_kv8_compiles_for_v5e(one_chip, C):
    B, H, Hk, dh, bs, nb, nseq = 4, 8, 1, 256, 16, 73, 18  # gemma-2b, kv8

    def f(q, kc, ks, vc, vs, bt, pos):
        return paged_attention_pallas(q, kc, ks, vc, vs, bt, pos, bits=8,
                                      block_size=bs, interpret=False)

    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((B, C, H, dh), jnp.float32), ((nb, bs, Hk, dh), jnp.uint8),
        ((nb, bs, Hk), jnp.float32), ((nb, bs, Hk, dh), jnp.uint8),
        ((nb, bs, Hk), jnp.float32), ((B, nseq), jnp.int32),
        ((B, C), jnp.int32))]
    assert "tpu_custom_call" in _compile_text(f, *shapes)


@pytest.mark.parametrize("tag", ["wq", "wo"])
def test_msgemm_linear_compiles_on_v5e_mesh(one_chip, topo, tag):
    """gemma-2b's wq shards over model=4; wo stays unsharded at d=3 (its
    k slice is no whole number of scale blocks) and still has to run
    the compiled kernel inside shard_map — XLA cannot partition it."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro import dispatch
    from repro.core import linear
    from repro.core.spec import QuantSpec
    from repro.distributed import sharding as shd

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("model",),
                axis_types=(AxisType.Auto,))
    spec = QuantSpec(mode="msgemm", d=D, scale_block=SB)
    params = jax.eval_shape(
        lambda k: linear.init(k, 2048, 2048, spec), jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, PartitionSpec())
    args = [jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        params), jax.ShapeDtypeStruct((4, 1, 2048), jnp.float32,
                                      sharding=rep)]
    policy = dispatch.ExecPolicy(backend="msgemm_pallas", interpret=False)

    def f(p, x):
        return dispatch.execute(p, x, spec, in_dim=2048, policy=policy,
                                shard_axes=shd.LINEAR_AXES[tag])

    with shd.use(mesh, "serve"):
        assert "tpu_custom_call" in _compile_text(f, *args)
