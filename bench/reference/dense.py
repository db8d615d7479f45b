"""Plain float32 forward of a decoder, for the check of ``correct``.

Straight ``jax.numpy`` at ``precision=highest``: no kernel, no cache, no
batching of requests, nothing imported from the program.  The weights
are drawn here from the run's seed, by the same keys and distributions
as the program's build (a normal draw of each (out, in) linear scaled by
in**-0.5, a truncated normal embedding and router), and put through the
configuration's stated weight format: symmetric 4-bit integers with one
scale per ``scale_block`` weights of a row (amax / 7).  So the reference
is the configuration's model in float32, whatever the program does with
those weights.  Biases, where the configuration has them, start at
zero, as the published initialisers draw them, so none is added.

Layers follow the configuration's block pattern (``program.model``, or
``program.block``): ``attn`` attends to every earlier position, ``local``
to the ``sliding_window`` latest, and ``moe`` replaces the MLP with a
router that sends each token to its ``num_experts_per_tok`` best experts,
weighted by a softmax over those experts' logits, plus the shared
experts.  No token is dropped for want of capacity, as in the published
models: a configuration whose ``capacity_factor`` lets the program drop
one is a different model, and the check says so.  ``qk_norm`` and the
softcaps follow the configuration too; a ``program.model`` field that
this reference does not model is an error.

Layers run one at a time, each over every sequence in turn, so that a
full-width model fits after the program's state is freed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
CONTROL_DTYPE = jnp.float8_e4m3fn
# program.model fields this reference follows (capacity and the dispatch
# groups change no result of a model that drops no token)
MODELLED = {"block_pattern", "sliding_window", "family", "num_experts",
            "num_experts_per_tok", "moe_d_ff", "num_shared_experts",
            "shared_expert_d_ff", "capacity_factor", "moe_groups",
            "qk_norm", "attn_logit_softcap", "final_logit_softcap"}


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a non-negative seed (a plain
    ``PRNGKey`` keeps 32).  The program's build is given the same key."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32),
                              seed // 2**32)


def _dims(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return d, h, c["num_key_value_heads"], c.get("head_dim") or d // h


def _quantized(key, k_in: int, m_out: int, scale_block: int):
    w = jax.random.normal(key, (m_out, k_in), F32) * k_in**-0.5
    kp = -(-k_in // scale_block) * scale_block
    wb = jnp.pad(w, ((0, 0), (0, kp - k_in))).reshape(m_out, -1, scale_block)
    amax = jnp.max(jnp.abs(wb), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 7.0)
    q = jnp.clip(jnp.round(wb / scale), -8, 7)
    return (q * scale).reshape(m_out, kp)[:, :k_in]


def _model(c) -> dict:
    m = c["program"].get("model", {})
    extra = sorted(set(m) - MODELLED)
    if extra:
        raise ValueError(f"the reference does not model program.model "
                         f"fields {extra}")
    return m


def _kinds(c) -> list[str]:
    return list(_model(c).get("block_pattern")
                or [c["program"].get("block", "attn")])


def _mlp_weights(c, key, ff: int) -> dict:
    d = c["hidden_size"]
    sb = c["program"]["quant"]["scale_block"]
    km = jax.random.split(key, 3)
    w = {"up": _quantized(km[0], d, ff, sb),
         "down": _quantized(km[1], ff, d, sb)}
    if _gated(c):
        w["gate"] = _quantized(km[2], d, ff, sb)
    return w


def _moe_weights(c, key) -> dict:
    """Router (E, d), experts stacked (E, ...), and the shared experts
    as one MLP of their summed width."""
    d = c["hidden_size"]
    m = _model(c)
    E, eff = m["num_experts"], m.get("moe_d_ff") or c["intermediate_size"]
    sb = c["program"]["quant"]["scale_block"]
    k3 = jax.random.split(key, 3)
    w = {"router": jax.random.truncated_normal(k3[0], -2.0, 2.0, (E, d),
                                               F32) * d**-0.5}
    shapes = {"up": (d, eff), "down": (eff, d)}
    if _gated(c):
        shapes["gate"] = (d, eff)
    for j, name in enumerate(("up", "down", "gate")):
        if name in shapes:
            keys = jax.random.split(jax.random.fold_in(k3[1], j), E)
            w[name] = jax.lax.map(
                lambda k, s=shapes[name]: _quantized(k, *s, sb), keys)
    if m.get("num_shared_experts"):
        sff = (m.get("shared_expert_d_ff")
               or m["num_shared_experts"] * eff)
        w["shared"] = _mlp_weights(c, k3[2], sff)
    return w


def _layer_weights(c, key, kind: str = "attn"):
    d, h, hk, dh = _dims(c)
    sb = c["program"]["quant"]["scale_block"]
    k8 = jax.random.split(key, 8)
    ka = jax.random.split(k8[0], 4)
    w = {"wq": _quantized(ka[0], d, h * dh, sb),
         "wk": _quantized(ka[1], d, hk * dh, sb),
         "wv": _quantized(ka[2], d, hk * dh, sb),
         "wo": _quantized(ka[3], h * dh, d, sb)}
    if kind == "moe":
        w["moe"] = _moe_weights(c, k8[1])
    elif kind in ("attn", "local"):
        w.update(_mlp_weights(c, k8[1], c["intermediate_size"]))
    else:
        raise ValueError(f"the reference has no {kind!r} block")
    return w


def _gated(c) -> bool:
    return c["program"]["mlp_activation"] in ("swiglu", "geglu")


def _eps(c) -> float:
    return c.get("norm_epsilon", c.get("rms_norm_eps"))


def _rms(c, x):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + _eps(c))


def _norm(c, x):
    if c["program"]["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + _eps(c))
    return _rms(c, x)


def _softcap(x, cap: float):
    return cap * jnp.tanh(x / cap) if cap else x


def _act(c, x):
    name = c["program"]["mlp_activation"]
    if name in ("gelu", "geglu"):  # the tanh form (gelu_pytorch_tanh)
        return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                       * (x + 0.044715 * x ** 3)))
    return x / (1 + jnp.exp(-x))  # silu


def _rope(x, theta: float):
    """Rotate the two halves of each head (x (S, H, Dh))."""
    S, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _in(a, low):
    """A linear's input, rounded to the control's precision with ``low``."""
    return a.astype(CONTROL_DTYPE).astype(F32) if low else a


def _mlp(c, w, a, low):
    def lin(name, a):
        return _in(a, low) @ w[name].T

    if _gated(c):
        u = _act(c, lin("gate", a)) * lin("up", a)
    else:
        u = _act(c, lin("up", a))
    return lin("down", u)


def _moe(c, w, a, low):
    """Each token through its best experts, weighted by the softmax of
    their router logits, plus the shared experts; a (S, d)."""
    m = _model(c)
    logits = _in(a, low) @ w["router"].T
    top, idx = jax.lax.top_k(logits, m["num_experts_per_tok"])
    gates = jax.nn.softmax(top, -1)

    def expert(e, y):
        share = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)[:, None]
        we = {k: w[k][e] for k in ("up", "down", "gate") if k in w}
        return y + share * _mlp(c, we, a, low)

    y = jax.lax.fori_loop(0, m["num_experts"], expert, jnp.zeros_like(a))
    if "shared" in w:
        y = y + _mlp(c, w["shared"], a, low)
    return y


def _block(c, w, x, low, kind: str = "attn"):
    """One block of ``kind`` over one sequence x (S, d).  ``low`` rounds
    every linear's input to the control's precision."""
    d, h, hk, dh = _dims(c)
    S = x.shape[0]
    m = _model(c)

    def lin(name, a):
        return _in(a, low) @ w[name].T

    def heads(y, n):
        y = y.reshape(S, n, dh)
        return _rms(c, y) if m.get("qk_norm") else y

    a = _norm(c, x)
    q = _rope(heads(lin("wq", a), h), c["rope_theta"])
    k = _rope(heads(lin("wk", a), hk), c["rope_theta"])
    v = lin("wv", a).reshape(S, hk, dh)
    q = q.reshape(S, hk, h // hk, dh)
    s = jnp.einsum("qhgd,khd->hgqk", q, k) / math.sqrt(dh)
    s = _softcap(s, m.get("attn_logit_softcap", 0.0))
    pos = jnp.arange(S)
    seen = pos[None, :] <= pos[:, None]
    if kind == "local":
        seen &= pos[None, :] > pos[:, None] - m["sliding_window"]
    s = jnp.where(seen, s, -jnp.inf)
    o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), v)
    x = x + lin("wo", o.reshape(S, h * dh))
    a = _norm(c, x)
    if kind == "moe":
        return x + _moe(c, w["moe"], a, low)
    return x + _mlp(c, w, a, low)


def _gaps(c, head, r, ctl, target):
    """Per position of one sequence: how far the target's logit, and
    the logit of the control's first choice, lie below the best."""
    cap = _model(c).get("final_logit_softcap", 0.0)
    lr = _softcap(_norm(c, r) @ head.T, cap)
    lc = _softcap(_in(_norm(c, ctl), True) @ head.T, cap)
    best = lr.max(-1)
    pick = jnp.argmax(lc, -1)
    return (best - jnp.take_along_axis(lr, target[:, None], -1)[:, 0],
            best - jnp.take_along_axis(lr, pick[:, None], -1)[:, 0])


def readings(c: dict, seed: int, seqs, *, length: int | None = None,
             control: bool = False) -> dict:
    """Widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``seqs``.

    ``seqs``: (prompt, served) pairs of token-id sequences, padded to
    ``length`` positions (the engine's limit: then every run of a cell
    runs the same programs, which the compile cache keeps).  With
    ``control`` the same forward also runs with every linear's input in
    float8 (e4m3), and the gap of the token it puts first is read at the
    same positions: that is the control, which a sound limit fails.
    """
    d, _, _, _ = _dims(c)
    L, V = c["num_hidden_layers"], c["vocab_size"]
    S = max([len(p) + len(s) - 1 for p, s in seqs] + [length or 0])
    S = -(-S // 128) * 128
    toks = np.zeros((len(seqs), S), np.int32)
    targets = np.zeros((len(seqs), S), np.int32)
    scored = np.zeros((len(seqs), S), bool)
    for i, (p, s) in enumerate(seqs):
        seq = list(p) + list(s)[:-1]
        toks[i, :len(seq)] = seq
        targets[i, len(p) - 1:len(seq)] = s
        scored[i, len(p) - 1:len(seq)] = True
    ks = jax.random.split(seed_key(seed), 6)
    streams = (False, True) if control else (False,)
    with jax.default_matmul_precision("highest"):
        emb = jax.jit(lambda k: jax.random.truncated_normal(
            k, -2.0, 2.0, (V, d), F32))(ks[0])
        xs = {low: jnp.take(emb, jnp.asarray(toks), axis=0)
              for low in streams}
        # layer g * P + i is group g's block i of the pattern (period P)
        kinds = _kinds(c)
        P = len(kinds)
        group_keys = [jax.random.split(jax.random.fold_in(ks[1], i), L // P)
                      for i in range(P)]
        draw = {kind: jax.jit(lambda k, kind=kind: _layer_weights(c, k, kind))
                for kind in set(kinds)}
        run = {kind: jax.jit(lambda w, X, low, kind=kind: jax.lax.map(
            lambda x: _block(c, w, x, low, kind), X), static_argnums=2)
            for kind in set(kinds)}
        for layer in range(L):
            kind = kinds[layer % P]
            w = draw[kind](group_keys[layer % P][layer // P])
            xs = {low: run[kind](w, X, low) for low, X in xs.items()}
            del w
        if c["tie_word_embeddings"]:
            head = emb
        else:
            head = jax.jit(lambda k: _quantized(
                k, d, V, c["program"]["quant"]["scale_block"]))(ks[2])
        del emb
        ref, ctl = xs[False], xs.get(True, xs[False])
        gaps = jax.jit(lambda H, R, C, T: jax.lax.map(
            lambda a: _gaps(c, H, *a), (R, C, T)))(
                head, ref, ctl, jnp.asarray(targets))
        gap, ctl_gap = (np.asarray(g) for g in gaps)
    out = {"positions": int(scored.sum()),
           "max_logit_gap": float(gap[scored].max())}
    if control:
        out["control_max_logit_gap"] = float(ctl_gap[scored].max())
    return out
