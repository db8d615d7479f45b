"""Plain float32 forward of a dense decoder, for the check of ``correct``.

Straight ``jax.numpy`` at ``precision=highest``: no kernel, no cache, no
batching of requests, nothing imported from the program.  The weights
are drawn here from the run's seed, by the same keys and distributions
as the program's build (a normal draw of each (out, in) linear scaled by
in**-0.5, a truncated normal embedding), and put through the
configuration's stated weight format: symmetric 4-bit integers with one
scale per ``scale_block`` weights of a row (amax / 7).  So the reference
is the configuration's model in float32, whatever the program does with
those weights.  Biases, where the configuration has them, start at
zero, as the published initialisers draw them, so none is added.

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


def _layer_weights(c, key):
    d, h, hk, dh = _dims(c)
    ff = c["intermediate_size"]
    sb = c["program"]["quant"]["scale_block"]
    k8 = jax.random.split(key, 8)
    ka = jax.random.split(k8[0], 4)
    km = jax.random.split(k8[1], 3)
    w = {"wq": _quantized(ka[0], d, h * dh, sb),
         "wk": _quantized(ka[1], d, hk * dh, sb),
         "wv": _quantized(ka[2], d, hk * dh, sb),
         "wo": _quantized(ka[3], h * dh, d, sb),
         "up": _quantized(km[0], d, ff, sb),
         "down": _quantized(km[1], ff, d, sb)}
    if _gated(c):
        w["gate"] = _quantized(km[2], d, ff, sb)
    return w


def _gated(c) -> bool:
    return c["program"]["mlp_activation"] in ("swiglu", "geglu")


def _eps(c) -> float:
    return c.get("norm_epsilon", c.get("rms_norm_eps"))


def _norm(c, x):
    eps = _eps(c)
    if c["program"]["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)


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


def _block(c, w, x, low):
    """One dense block over one sequence x (S, d).  ``low`` rounds every
    linear's input to the control's precision."""
    d, h, hk, dh = _dims(c)
    S = x.shape[0]

    def lin(name, a):
        if low:
            a = a.astype(CONTROL_DTYPE).astype(F32)
        return a @ w[name].T

    a = _norm(c, x)
    q = _rope(lin("wq", a).reshape(S, h, dh), c["rope_theta"])
    k = _rope(lin("wk", a).reshape(S, hk, dh), c["rope_theta"])
    v = lin("wv", a).reshape(S, hk, dh)
    q = q.reshape(S, hk, h // hk, dh)
    s = jnp.einsum("qhgd,khd->hgqk", q, k) / math.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), v)
    x = x + lin("wo", o.reshape(S, h * dh))
    a = _norm(c, x)
    if _gated(c):
        u = _act(c, lin("gate", a)) * lin("up", a)
    else:
        u = _act(c, lin("up", a))
    return x + lin("down", u)


def _gaps(c, head, r, ctl, target):
    """Per position of one sequence: how far the target's logit, and
    the logit of the control's first choice, lie below the best."""
    lr = _norm(c, r) @ head.T
    lc = _norm(c, ctl).astype(CONTROL_DTYPE).astype(F32) @ head.T
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
        layer_keys = jax.random.split(jax.random.fold_in(ks[1], 0), L)
        draw = jax.jit(lambda k: _layer_weights(c, k))
        run = jax.jit(lambda w, X, low: jax.lax.map(
            lambda x: _block(c, w, x, low), X), static_argnums=2)
        for i in range(L):
            w = draw(layer_keys[i])
            xs = {low: run(w, X, low) for low, X in xs.items()}
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
