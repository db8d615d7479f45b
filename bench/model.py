"""The system under test, built from a configuration file.

A configuration file (``bench/configs/<name>.json``) holds the published
sizes under the model's own config keys, as run, and a ``program``
group that names how the program runs them: block kind, norm, MLP
activation, activation and KV-cache dtypes, and the weight format.

The published keys map onto the program's ``ModelConfig`` (``hidden_size``
to ``d_model``, ``num_attention_heads`` to ``num_heads``, ...).  Then an
optional ``program.model`` group is applied verbatim as ``ModelConfig``
fields: ``block_pattern`` (a list, e.g. ``["local", "attn"]``),
``sliding_window``, ``family``, ``num_experts``, ``num_experts_per_tok``,
``moe_d_ff``, ``num_shared_experts``, ``shared_expert_d_ff``,
``capacity_factor``, ``qk_norm``, the softcaps, or any field a later
program adds.  ``program.block`` names a one-kind pattern.  Nothing here
is specific to one model, so a new configuration is a new file.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference.dense import seed_key

SERVE_RULES = "serve"  # the serving engine's default sharding rules


def model_fields(c: dict) -> dict:
    """The ``program.model`` group of ``c``, every key a ``ModelConfig``
    field; a key that is not one is an error that names it."""
    from repro.models.config import ModelConfig

    extra = dict(c["program"].get("model", {}))
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(extra) - known)
    if unknown:
        raise ValueError(f"{c['name']}: program.model names "
                         f"{', '.join(map(repr, unknown))}, which "
                         f"ModelConfig does not have")
    if "block_pattern" in extra:
        extra["block_pattern"] = tuple(extra["block_pattern"])
    return extra


def model_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.core.spec import QuantSpec
    from repro.models.config import ModelConfig

    p = c["program"]
    fields = dict(
        name=c["name"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or 0,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]),
        mlp_activation=p["mlp_activation"], norm=p["norm"],
        tie_embeddings=c["tie_word_embeddings"], dtype=p["dtype"],
        quant=QuantSpec(**p["quant"]))
    if "block" in p:
        fields["block_pattern"] = (p["block"],)
    fields.update(model_fields(c))
    return ModelConfig(**fields)


def build(c: dict, seed: int, mesh=None):
    """(params, cfg): weights drawn on the device from ``seed`` by the
    program's own build, quantized as each layer group is drawn.  Under
    ``mesh`` each chip draws only its shard, laid out by the serving
    engine's rules, so a model larger than one chip can be built."""
    from repro.models import transformer as T

    cfg = model_config(c)
    init = lambda k: T.init_params(k, cfg)  # noqa: E731
    key = seed_key(seed)
    out = None
    if mesh is not None:
        from repro.distributed import sharding as shd

        out = shd.shardings(jax.eval_shape(init, key), mesh, SERVE_RULES)
    return jax.jit(init, out_shardings=out)(key), cfg


def kv_dtype(c: dict):
    return jnp.dtype(c["program"]["kv_cache_dtype"])
