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
import re

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


# (m, k) of every linear of the benchmark's configurations: StarCoder2-15B
# (wq/wo, wk/wv, up, down, lm_head), then Phi-3-mini (attention, gate/up,
# down, lm_head)
CELL_LINEARS = [(6144, 6144), (512, 6144), (24576, 6144), (6144, 24576),
                (49152, 6144), (3072, 3072), (8192, 3072), (3072, 8192),
                (32064, 3072)]
HLO_LINE = re.compile(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")


@pytest.mark.parametrize("rows", [8, 16])
@pytest.mark.parametrize("m,k", CELL_LINEARS)
def test_msgemm_mxu_compiles_for_v5e(one_chip, m, k, rows):
    """The MXU backend compiles at the cells' linear shapes, its kernel
    is named ``msgemm_mxu`` (what bench/kernels/msgemm_mxu.json matches),
    and the weights meet no XLA op on the way in: every instruction that
    holds a weight-sized array, in either orientation, is a parameter, a
    bitcast, the kernel, or an asynchronous copy into VMEM that XLA
    schedules for a small operand (the kernel's own read, moved ahead)."""
    from repro import dispatch
    from repro.core import linear
    from repro.core.spec import QuantSpec

    spec = QuantSpec(mode="msgemm", d=D, scale_block=SB)
    params = jax.eval_shape(
        lambda key: linear.init(key, k, m, spec), jax.random.PRNGKey(0))
    args = [jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params),
        jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)]
    policy = dispatch.ExecPolicy(backend="msgemm_mxu", interpret=False)
    text = _compile_text(lambda p, x: dispatch.execute(
        p, x, spec, in_dim=k, policy=policy), *args)
    assert re.search(r"%msgemm_mxu(\.\d+)? = .*tpu_custom_call", text)
    weights = {dims for a in params.values()
               for dims in (a.shape, a.shape[::-1])}
    ops = [(hit.group(3), line) for line in text.splitlines()
           if (hit := HLO_LINE.match(line)) and tuple(
               int(v) for v in hit.group(2).split(",") if v) in weights]
    kept = ("parameter", "bitcast", "custom-call", "copy-done")
    assert ops and all(op in kept for op, _ in ops), \
        [line for _, line in ops]


# (m, k) of untied vocab-sized heads: StarCoder2-15B, Phi-3-mini,
# qwen2-moe-a2.7b and llama4-maverick (src/repro/configs)
HEADS = [(49152, 6144), (32064, 3072), (151936, 2048), (202048, 5120)]


@pytest.mark.parametrize("m,k", HEADS)
def test_msgemm_mxu_head_prefill_compiles_for_v5e(one_chip, m, k):
    """A vocab-sized head prefilled at 128 rows, with the float32 logits
    the model asks of it, fits VMEM: the output stripe is split into m
    groups where it would not (a single (128, m) f32 stripe, double
    buffered, is 171-224 MB at the two largest vocabularies)."""
    from repro.kernels import msgemm_mxu as mx

    rows = 128
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    args = [sds((m, -(-k // D)), jnp.int32), sds((m, -(-k // SB)),
                                                  jnp.float32),
            sds((rows, k), jnp.bfloat16)]
    text = _compile_text(lambda i, s, x: ops.msgemm_mxu(
        i, s, x, D, scale_block=SB, interpret=False,
        epilogue=Epilogue(out_dtype="float32")), *args)
    assert re.search(r"%msgemm_mxu(\.\d+)? = .*tpu_custom_call", text)
    nm = -(-m // 512)
    groups, _ = mx.m_groups(nm, 512, rows, 4 + 2 * 4)
    assert (groups > 1) == (nm * 512 * rows * 12 > mx.STRIPE_BUDGET)


def test_scanned_decode_step_slices_each_layer_weight_once(one_chip):
    """The decode step of a scanned layer stack at StarCoder2-15B's widths
    (13 layers, as the code-decode cell runs it) holds no unpack, pack,
    pad or transpose of a stored weight around ``msgemm_mxu``.  What the
    scan does to each layer's ``idx`` and ``scales`` is one
    ``dynamic-slice`` apiece out of the stacked arrays, bitcast to the
    kernel's view: a copy of every weight in every step, which the
    standalone linear does not pay.  Passing the stacked arrays and the
    layer index into the kernel would take that count to zero."""
    import numpy as np

    from repro import dispatch
    from repro.core.spec import QuantSpec
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.runtime import serve as SV

    cfg = ModelConfig(
        name="starcoder2-15b-stage13", family="dense", num_layers=13,
        d_model=6144, num_heads=48, num_kv_heads=4, head_dim=128,
        d_ff=24576, vocab_size=49152, max_seq_len=256,
        block_pattern=("attn",), mlp_activation="gelu", norm="layernorm",
        tie_embeddings=False, dtype="bfloat16",
        quant=QuantSpec(mode="msgemm", d=D, scale_block=SB))
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = place(jax.eval_shape(lambda key: T.init_params(key, cfg),
                                  jax.random.PRNGKey(0)))
    bs, rows, width = 16, 8, 128
    pool = place(jax.eval_shape(lambda: SV.init_paged_cache(
        cfg, rows * (width // bs) + 1, bs, jnp.bfloat16)))

    def step(params, pool, tokens, positions, ws, vs, last):
        logits, pool = SV.paged_step(params, cfg, tokens, pool, positions,
                                     ws, vs, last)
        return jnp.argmax(logits, -1), pool

    ints = [jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)
            for s in ((rows, 1),) * 3 + ((rows, width), (rows,))]
    policy = dispatch.ExecPolicy(backend="msgemm_mxu", interpret=False)
    with dispatch.using_policy(policy):
        text = _compile_text(step, params, pool, *ints)
    stacked = [a.shape for path, a in
               jax.tree_util.tree_leaves_with_path(params["blocks"])
               if jax.tree_util.keystr(path[-1:]) in ("['idx']",
                                                       "['scales']")]
    weights = set()
    for shape in stacked:
        one = shape[1:]
        weights |= {shape, (1,) + one, one, one[::-1]}
    ops_ = [(hit.group(3), line) for line in text.splitlines()
            if (hit := HLO_LINE.match(line)) and tuple(
                int(v) for v in hit.group(2).split(",") if v) in weights]
    kept = ("parameter", "get-tuple-element", "bitcast", "fusion",
            "custom-call", "copy-start", "copy-done", "dynamic-slice")
    assert all(op in kept for op, _ in ops_), \
        [line for op, line in ops_ if op not in kept]
    assert len(stacked) == 12  # idx and scales of wq, wk, wv, wo, up, down
    assert sum(op == "dynamic-slice" for op, _ in ops_) == len(stacked)
    assert len(re.findall(r"%msgemm_mxu(?:\.\d+)? = ", text)) == 7


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


@pytest.mark.parametrize("backend", ["msgemm_pallas", "msgemm_mxu"])
@pytest.mark.parametrize("tag", ["wq", "wo"])
def test_msgemm_linear_compiles_on_v5e_mesh(one_chip, topo, tag, backend):
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
    policy = dispatch.ExecPolicy(backend=backend, interpret=False)

    def f(p, x):
        return dispatch.execute(p, x, spec, in_dim=2048, policy=policy,
                                shard_axes=shd.LINEAR_AXES[tag])

    with shd.use(mesh, "serve"):
        assert "tpu_custom_call" in _compile_text(f, *args)
