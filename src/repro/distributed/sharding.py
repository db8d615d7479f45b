"""Logical-axis sharding (MaxText-style, raw JAX).

Tensors are annotated with *logical* axis names; a rule table maps logical
axes to mesh axes.  Spec construction is shape-aware and greedy:

* logical axes are resolved in PRIORITY order (e.g. 'expert' grabs the
  'model' mesh axis before 'mlp' does, 'kvheads' before 'kv_seq');
* a mesh axis is used at most once per spec;
* a candidate mesh axis is skipped when the dim size is not divisible by
  its size (the divisibility fallback chain of DESIGN.md §4 — e.g.
  qwen2-moe's 60 experts fall through to per-expert TP on mlp=1408).

Params and activations use different tables: params additionally shard
their 'embed'/'residual' dims over the data axis (ZeRO-3/FSDP), so the
llama4-400B train state fits 512 chips.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Resolution priority: earlier names grab contested mesh axes first.
PRIORITY = (
    "batch", "expert", "expert_out", "heads", "kvheads", "mlp", "vocab",
    "embed", "mamba_inner", "xl_inner", "kv_seq", "seq", "capacity",
    "stack", "layers", "head_dim", "conv", "state", "scales", "expert_in",
    "none",
)
assert PRIORITY.index("seq") > PRIORITY.index("heads")

# logical axis -> candidate mesh axes, tried in order.
ACT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),  # folded: batch shards over pod x data
    # sequence-parallel fallback: when heads/kvheads cannot take the model
    # axis (llama4: 40 heads, gemma-2b: 8 heads on model=16), activations
    # shard over seq instead, bounding the attention-logits footprint.
    # PRIORITY puts 'seq' after heads/kvheads/mlp, so it only fires when
    # those fail divisibility.
    "seq": ("model",),
    "kv_seq": ("model",),  # decode caches: shard seq when heads cannot
    "heads": ("model",),
    "kvheads": ("model",),
    "head_dim": (),
    "embed": (),
    "mlp": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    # dispatch capacity dim = (examples x per-example slots): the major
    # factor is the batch, so 'data' sharding stays representable; without
    # it the dispatch buffers replicate when E can't take 'model'
    # (qwen2-moe: 5.4 GB/device -> 335 MB).
    "capacity": ("data",),
    # Expert FFN weights: out-dim takes the first free of model/data, the
    # in (contraction) dim stays replicated.  With E | model (llama4,
    # jamba) experts are then fully (expert x data)-sharded with NO FSDP
    # gather — tokens move to experts (EP all-to-all), not weights to
    # tokens (EXPERIMENTS.md §Perf A).  With E unshardable (qwen2-moe 60)
    # this degrades gracefully to per-expert TP on 'model'.
    "expert_out": ("model", "data"),
    "expert_in": (),
    "mamba_inner": ("model",),
    "xl_inner": ("model",),
    "state": (),
    "conv": (),
    "stack": (),
    "layers": (),
    "scales": (),
    "none": (),
}

# Param tables: 2D FSDP x TP — big output dims on 'model', the residual
# ('embed') dim additionally on 'data' (ZeRO-3).  Expert FFN weights get
# the mlp dim on 'data' when 'model' is already taken by the expert dim:
# they are then fully 256-way sharded *without* any FSDP gather — tokens
# move to experts (EP all-to-all) instead of weights moving to tokens,
# which collapses the 400B-train collective term (EXPERIMENTS.md §Perf A).
PARAM_RULES: dict[str, tuple[str, ...]] = dict(
    ACT_RULES,
    embed=("data",),
    batch=(),
    kv_seq=(),
)

# A rule-set bundle selectable per run (cfg.logical_rules).
RULE_SETS = {
    "default": (ACT_RULES, PARAM_RULES),
    # serving at batch=1 (long_500k): nothing to gain from data-parallel
    # activations; keep params TP-only so no all-gathers on the hot path.
    "serve_tp": (
        dict(ACT_RULES, batch=()),
        dict(PARAM_RULES, embed=()),
    ),
    # batched serving (the continuous engine): activations keep the full
    # default table (batch over data, kvheads over model), but params
    # drop the embed/data FSDP dim — weights are TP-resident, so the
    # shard_map-wrapped quantized linears (repro.dispatch.shard) see
    # their storage sharding exactly match their in_specs and the hot
    # path issues no per-layer FSDP gathers.
    "serve": (ACT_RULES, dict(PARAM_RULES, embed=())),
}


class _Ctx(threading.local):
    mesh: Mesh | None = None
    rules: str = "default"


_CTX = _Ctx()


@contextmanager
def use(mesh: Mesh, rules: str = "default"):
    """Activate a mesh + rule set for logical constraints."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def active_mesh() -> Mesh | None:
    return _CTX.mesh


def active_rules() -> str:
    return _CTX.rules


def _resolve(axes: tuple, shape: tuple, mesh: Mesh, table: dict) -> P:
    """Greedy shape-aware logical->mesh resolution."""
    order = sorted(
        range(len(axes)),
        key=lambda i: PRIORITY.index(axes[i]) if axes[i] in PRIORITY else 99,
    )
    used: set[str] = set()
    out: list = [None] * len(axes)
    for i in order:
        name = axes[i]
        if name is None or name == "none":
            continue
        fold = name == "batch"  # only batch folds ('pod' x 'data')
        for cand in table.get(name, ()):
            if cand not in mesh.shape or cand in used:
                continue
            if shape[i] % mesh.shape[cand] == 0:
                out[i] = cand if out[i] is None else tuple(
                    (out[i] if isinstance(out[i], tuple) else (out[i],))
                    + (cand,))
                used.add(cand)
                if not fold:
                    break  # fallback semantics: first available candidate
        # combined divisibility for folded axes
        if isinstance(out[i], tuple):
            total = int(np.prod([mesh.shape[a] for a in out[i]]))
            if shape[i] % total != 0:
                out[i] = out[i][0]
    return P(*out)


def spec_for(axes: tuple, shape: tuple, *, mesh: Mesh | None = None,
             kind: str = "act", rules: str | None = None) -> P:
    mesh = mesh or _CTX.mesh
    if mesh is None:
        return P()
    act, par = RULE_SETS[rules or _CTX.rules]
    return _resolve(tuple(axes), tuple(shape), mesh, act if kind == "act" else par)


def constrain(x, *axes):
    """with_sharding_constraint on logical axes; no-op without a mesh."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"axes {axes} vs shape {x.shape}")
    spec = spec_for(axes, x.shape, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Param-tree spec inference
# ---------------------------------------------------------------------------
# Each linear/param leaf lives under a descriptive key; the table maps that
# key to logical axes of the *dense* (out, in) orientation.  Quantized
# layouts ('idx', 'u8', 'scales') inherit the same logical axes (their
# second dim is a packed function of 'in').  Leading stacked dims
# ('layers', 'expert') are prepended by the tree walker based on depth.

LINEAR_AXES: dict[str, tuple] = {
    "wq": ("heads", "embed"),
    "wk": ("kvheads", "embed"),
    "wv": ("kvheads", "embed"),
    "wo": ("embed", "heads"),
    "up": ("mlp", "embed"),
    "gate": ("mlp", "embed"),
    "down": ("embed", "mlp"),
    "router": ("expert", "embed"),
    "lm_head": ("vocab", "embed"),
    "in_proj": ("mamba_inner", "embed"),
    "x_proj": ("none", "mamba_inner"),
    "dt_proj": ("mamba_inner", "none"),
    "out_proj": ("embed", "mamba_inner"),
    "xl_up": ("xl_inner", "embed"),
    "xl_o": ("xl_inner", "embed"),
    "xl_gates": ("none", "xl_inner"),
    "xl_down": ("embed", "xl_inner"),
    "sl_w": ("embed", "none"),
    "sl_r": ("embed", "none"),
}
VECTOR_AXES: dict[str, tuple] = {
    "embedding": ("vocab", "embed"),
    "scale": ("none",),
    "bias": ("none",),
    "A_log": ("mamba_inner", "state"),
    "D": ("mamba_inner",),
    "conv_w": ("conv", "mamba_inner"),
    "conv_b": ("mamba_inner",),
    "xl_conv_w": ("conv", "xl_inner"),
    "xl_conv_b": ("xl_inner",),
    "xl_q": ("heads", "head_dim", "head_dim"),
    "xl_k": ("heads", "head_dim", "head_dim"),
    "xl_v": ("heads", "head_dim", "head_dim"),
}


def _leaf_axes(path: tuple, leaf_ndim: int) -> tuple:
    names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    # innermost linear-ish ancestor key
    anc = None
    for n in reversed(names):
        if n in LINEAR_AXES or n in VECTOR_AXES:
            anc = n
            break
    leaf = names[-1]
    is_expert = any(n == "experts" for n in names)
    if anc in LINEAR_AXES:
        base = LINEAR_AXES[anc]
        if is_expert and anc in ("up", "gate", "down"):
            base = ("expert_out", "expert_in")
        if leaf in ("w", "idx", "u8"):
            axes = base
        elif leaf == "scales":
            axes = (base[0], "scales")
        elif leaf == "codebook":
            axes = ("scales",)  # 16-entry value table: replicated
        elif leaf in ("b", "bias"):
            axes = (base[0],)
        else:
            axes = base
    elif anc in VECTOR_AXES:
        axes = VECTOR_AXES[anc]
    else:
        axes = ("none",) * leaf_ndim
    # prepend stacked dims (scan groups, experts)
    extra = leaf_ndim - len(axes)
    if extra < 0:
        axes = axes[-leaf_ndim:] if leaf_ndim else ()
        extra = 0
    prefix = []
    is_expert = any(n == "experts" for n in names)
    for e in range(extra):
        if is_expert and e == extra - 1 and anc in ("up", "gate", "down"):
            prefix.append("expert")
        else:
            prefix.append("layers")
    return tuple(prefix) + tuple(axes)


# Decode/prefill cache leaves (under the stacked (G, ...) block groups).
CACHE_AXES: dict[str, tuple] = {
    "k": ("batch", "kv_seq", "kvheads", "head_dim"),
    "v": ("batch", "kv_seq", "kvheads", "head_dim"),
    "cross_k": ("batch", "kv_seq", "kvheads", "head_dim"),
    "cross_v": ("batch", "kv_seq", "kvheads", "head_dim"),
    "ssm": ("batch", "mamba_inner", "state"),
    "conv": ("batch", "conv", "mamba_inner"),
    "C": ("batch", "heads", "head_dim", "head_dim"),
    "n": ("batch", "heads", "head_dim"),
    "m": ("batch", "heads"),
    "h": ("batch", "embed"),
    "c": ("batch", "embed"),
}


def cache_specs(cache_shape, mesh: Mesh, rules: str = "default"):
    """PartitionSpec pytree for a transformer.init_cache tree (leaves are
    stacked (G, ...) -> 'layers' prefix)."""

    def one(path, leaf):
        name = getattr(path[-1], "key", str(path[-1]))
        axes = CACHE_AXES.get(name, ("none",) * (len(leaf.shape) - 1))
        axes = ("layers",) + tuple(axes)
        if len(axes) != len(leaf.shape):  # xlstm 'm' vs mamba trees etc.
            axes = ("layers",) + ("none",) * (len(leaf.shape) - 1)
        return spec_for(axes, leaf.shape, mesh=mesh, kind="act", rules=rules)

    return jax.tree_util.tree_map_with_path(one, cache_shape)


# Paged-serving KV block pools (runtime.serve.init_paged_cache): leaves
# are (G, num_blocks, block_size, Hk, Dh).  The pool has no batch dim —
# sequences own block subsets via host-side tables — so only the
# kvheads/head_dim tail shards (kvheads over 'model' per ACT_RULES);
# the block and slot dims stay replicated: scatter/gather by flat slot
# id must find every sequence's blocks on every data shard.
PAGED_CACHE_AXES: dict[str, tuple] = {
    # full-precision values *or* quantized u8 codes (last dim Dh or the
    # packed Dhp — 'head_dim' maps to () in serve rules, so both shard
    # identically: replicated tail, kvheads on 'model')
    "k": ("layers", "none", "none", "kvheads", "head_dim"),
    "v": ("layers", "none", "none", "kvheads", "head_dim"),
    # quantized-pool scale leaves (repro.kvq.pool): (G, nb, bs, Hk) f32,
    # same (block, slot) replication + kvheads placement as the codes so
    # a flat slot id addresses codes and scales on the same shard
    "k_scale": ("layers", "none", "none", "kvheads"),
    "v_scale": ("layers", "none", "none", "kvheads"),
}


def paged_cache_specs(pool_shape, mesh: Mesh, rules: str = "default"):
    """PartitionSpec pytree for a runtime.serve.init_paged_cache tree."""

    def one(path, leaf):
        name = getattr(path[-1], "key", str(path[-1]))
        axes = PAGED_CACHE_AXES.get(
            name, ("layers",) + ("none",) * (len(leaf.shape) - 1))
        return spec_for(axes, leaf.shape, mesh=mesh, kind="act", rules=rules)

    return jax.tree_util.tree_map_with_path(one, pool_shape)


def batch_specs(batch_shape, mesh: Mesh, rules: str = "default"):
    """PartitionSpec pytree for data batches / serve inputs by rank."""

    def one(path, leaf):
        name = getattr(path[-1], "key", str(path[-1]))
        if name in ("token", "pos"):
            axes = ("batch",)
        else:
            axes = {1: ("batch",), 2: ("batch", "seq"),
                    3: ("batch", "seq", "embed")}[len(leaf.shape)]
        return spec_for(axes, leaf.shape, mesh=mesh, kind="act", rules=rules)

    return jax.tree_util.tree_map_with_path(one, batch_shape)


def param_specs(params_shape, mesh: Mesh, rules: str = "default"):
    """Infer a PartitionSpec pytree for a params (shape) pytree."""

    def one(path, leaf):
        shape = leaf.shape
        axes = _leaf_axes(path, len(shape))
        return spec_for(axes, shape, mesh=mesh, kind="param", rules=rules)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def constrain_params(tree, *, int8_gather: bool = False):
    """Pin a param (sub)tree to its storage sharding — used at the top of
    each scanned layer group so the FSDP all-gather happens per-group
    inside the loop body, not on the full (G, ...) stack outside it
    (full-stack gather = G x the memory; see EXPERIMENTS.md §Perf).

    int8_gather=True additionally routes FSDP('data')-sharded float
    leaves through the explicit int8 all-gather wire format."""
    mesh = _CTX.mesh
    if mesh is None:
        return tree

    def one(path, leaf):
        axes = _leaf_axes(path, leaf.ndim)
        spec = spec_for(axes, leaf.shape, mesh=mesh, kind="param")
        leaf = jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))
        if int8_gather and jnp.issubdtype(leaf.dtype, jnp.floating):
            from repro.distributed.collectives import int8_all_gather

            leaf = int8_all_gather(leaf, mesh, spec, axis="data")
        return leaf

    return jax.tree_util.tree_map_with_path(one, tree)


def shardings(tree_shape, mesh: Mesh, rules: str = "default"):
    specs = param_specs(tree_shape, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))
