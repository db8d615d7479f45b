"""End-to-end behaviour tests for the system: train->quantize->serve
workflow, generation semantics, serve consistency across quant modes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.linear import QuantConfig
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.quant import quantize_model
from repro.quant.quantize import quantized_size_bytes
from repro.runtime import serve as SV

CFG = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  d_ff=128, vocab_size=211, max_seq_len=128)


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def test_generate_greedy_deterministic(params):
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                          CFG.vocab_size)}
    out1 = SV.generate(params, CFG, batch, max_new_tokens=6)
    out2 = SV.generate(params, CFG, batch, max_new_tokens=6)
    assert out1.shape == (2, 6)
    assert np.array_equal(out1, out2)


def test_generate_matches_stepwise_forward(params):
    """Greedy generation == repeatedly running the full forward."""
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0,
                              CFG.vocab_size)
    gen = SV.generate(params, CFG, {"tokens": toks}, max_new_tokens=4)
    cur = toks
    for i in range(4):
        logits, _ = T.forward(params, CFG, {"tokens": cur})
        nxt = jnp.argmax(logits[:, -1], -1)
        assert int(nxt[0]) == int(gen[0, i]), f"divergence at step {i}"
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)


def test_train_quantize_serve_workflow(params):
    """The paper's deployment story: dense weights -> int4 -> msGeMM serve
    produces the same generations as the int4-dequant reference."""
    qc = QuantConfig(mode="msgemm", d=3, scale_block=36)
    p_ms = quantize_model(params, CFG, qc)
    c_ms = CFG.replace(quant=qc)
    qc2 = QuantConfig(mode="int4_dequant", d=3, scale_block=36)
    p_dq = quantize_model(params, CFG, qc2)
    c_dq = CFG.replace(quant=qc2)

    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                                          CFG.vocab_size)}
    lg_ms, _ = T.forward(p_ms, c_ms, batch)
    lg_dq, _ = T.forward(p_dq, c_dq, batch)
    # same int4 weights, two algorithms -> near-identical logits
    np.testing.assert_allclose(lg_ms, lg_dq, rtol=2e-3, atol=2e-3)

    # quantized weights are materially smaller
    dense_bytes = quantized_size_bytes(params)
    ms_bytes = quantized_size_bytes(p_ms)
    assert ms_bytes < 0.55 * dense_bytes  # packed_idx ~10.7 bits + scales


def test_quantized_generation_quality(params):
    """int4 quantization preserves the logit structure (random-init logits
    are near-uniform, so token agreement is a poor metric; correlation of
    the next-token distribution is the right invariant)."""
    qc = QuantConfig(mode="msgemm", d=2, scale_block=16)
    p_q = quantize_model(params, CFG, qc)
    c_q = CFG.replace(quant=qc)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (4, 8), 0,
                                          CFG.vocab_size)}
    lg_d, _ = T.forward(params, CFG, batch)
    lg_q, _ = T.forward(p_q, c_q, batch)
    corr = float(jnp.corrcoef(lg_d.ravel(), lg_q.ravel())[0, 1])
    assert corr > 0.95, f"quantized logits decorrelated ({corr})"


def test_temperature_sampling_changes_output(params):
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0,
                                          CFG.vocab_size)}
    greedy = SV.generate(params, CFG, batch, max_new_tokens=8)
    hot = SV.generate(params, CFG, batch, max_new_tokens=8, temperature=5.0,
                      key=jax.random.PRNGKey(9))
    assert not np.array_equal(greedy, hot)


def test_long_decode_states_bounded():
    """Recurrent archs decode with O(1) state (the long_500k premise)."""
    from repro import configs

    cfg = configs.get_smoke("xlstm_1b3")
    c64 = T.init_cache(cfg, 2, 64)
    c4096 = T.init_cache(cfg, 2, 4096)
    b64 = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(c64))
    b4096 = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(c4096))
    assert b64 == b4096  # state size independent of context length


def test_quantized_init_matches_quantize_model():
    """Drawing a quantized config directly (what the serve CLI does, so a
    full-width model never holds its dense tree) builds the same tree,
    bit for bit, as quantizing the dense init in one program.  (Op by op
    outside jit, XLA may round a scale differently by one ulp.)"""
    from repro.core.spec import QuantSpec

    spec = QuantSpec(mode="msgemm", d=3, scale_block=36)
    key = jax.random.PRNGKey(5)
    direct = jax.jit(lambda k: T.init_params(k, CFG.replace(quant=spec)))(key)
    ref = jax.jit(lambda k: quantize_model(T.init_params(k, CFG), CFG,
                                           spec))(key)
    assert jax.tree.structure(direct) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(direct), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    entry points cache at one fixed, git-ignored directory in the
    checkout."""
    import pathlib

    from repro.launch import cache

    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = cache.use_compile_cache()
        root = pathlib.Path(__file__).resolve().parents[1]
        if env_dir is None:
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert ".jax_cache/" in (root / ".gitignore").read_text()
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
