"""The system under test, built from a configuration file.

A configuration file (``bench/configs/<name>.json``) holds the published
sizes under the model's own config keys, as run, and a ``program``
group that names how the program runs them: block kind, norm, MLP
activation, activation and KV-cache dtypes, and the weight format.
Nothing here is specific to one model, so a new configuration is a new
file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.dense import seed_key


def model_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.core.spec import QuantSpec
    from repro.models.config import ModelConfig

    p = c["program"]
    return ModelConfig(
        name=c["name"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or 0,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        max_seq_len=c["max_position_embeddings"],
        block_pattern=(p["block"],), rope_theta=float(c["rope_theta"]),
        mlp_activation=p["mlp_activation"], norm=p["norm"],
        tie_embeddings=c["tie_word_embeddings"], dtype=p["dtype"],
        quant=QuantSpec(**p["quant"]))


def build(c: dict, seed: int):
    """(params, cfg): weights drawn on the device from ``seed`` by the
    program's own build, quantized as each layer group is drawn."""
    from repro.models import transformer as T

    cfg = model_config(c)
    params = jax.jit(lambda k: T.init_params(k, cfg))(seed_key(seed))
    return params, cfg


def kv_dtype(c: dict):
    return jnp.dtype(c["program"]["kv_cache_dtype"])
