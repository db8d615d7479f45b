"""The work a step needs, from the configuration's shapes alone.

These counts do not depend on how the program computes: a linear of
(m, k) over b useful rows needs 2*m*k*b operations and must read its
4-bit codes (m*k/2 bytes), its scales (one per ``scale_block`` weights
of a row) and its bf16 activations, and write its bf16 outputs.  Its
least time on a chip is the larger of operations over the chip's bf16
peak and bytes over its HBM bandwidth (``peaks.json``); on several chips
the least time of a step is taken over all their peaks together.

Each layer is counted by its kind, as the configuration file names it
(``program.model.block_pattern``, or ``program.block``):

* ``attn``: q, k, v and o projections and the MLP; attention reads every
  earlier position of the sequence.
* ``local``: the same linears; attention reads at most the
  ``sliding_window`` latest positions, so its operations are counted
  over ``min(position, window)`` per row (``Step.ctx_local``).
* ``moe``: the attention linears, the router (hidden x experts held),
  the shared experts (one MLP of their summed width) and, in
  ``model_flops`` only, the routed experts: ``num_experts_per_tok x held
  / published`` expert MLPs per token, the share's expected work under
  uniform routing.  The routed experts and the router count nothing in
  the least time of a step's linears: how many rows each held expert
  gets is not reported by the program, and neither runs as a linear
  kernel that ``bench/kernels/*.json`` matches.  So a share of the
  roofline under-reads an expert layer and can never over-read it.

A share of a roofline or of a peak computed from these counts stays at
or below 100% whatever the routing and the step mix.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
ACT_BYTES = 2  # bf16 activations in and out
KINDS = ("attn", "local", "moe")
# the source's key for the number of routed experts, by model family
EXPERT_KEYS = ("num_experts", "num_local_experts", "n_routed_experts")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def _model(c: dict) -> dict:
    return c["program"].get("model", {})


def layer_kinds(c: dict) -> list[str]:
    """The kind of each layer, in order."""
    pattern = (_model(c).get("block_pattern")
               or [c["program"].get("block", "attn")])
    bad = sorted(set(pattern) - set(KINDS))
    if bad:
        raise ValueError(f"no work counts for block kinds {bad}")
    n = c["num_hidden_layers"]
    return [pattern[i % len(pattern)] for i in range(n)]


def window(c: dict) -> int:
    """Positions a ``local`` layer attends to; 0 where no layer is local."""
    if "local" not in layer_kinds(c):
        return 0
    return int(_model(c)["sliding_window"])


def _heads(c: dict) -> tuple[int, int, int]:
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    return h, hk, c.get("head_dim") or c["hidden_size"] // h


def _mlp(c: dict, prefix: str, ff: int) -> list[tuple[str, int, int]]:
    d = c["hidden_size"]
    out = [(f"{prefix}up", ff, d), (f"{prefix}down", d, ff)]
    if c["program"]["mlp_activation"] in ("swiglu", "geglu"):
        out.append((f"{prefix}gate", ff, d))
    return out


def _experts(c: dict) -> tuple[int, int, int, int]:
    """(held, published, per token, expert width) of a moe layer."""
    m = _model(c)
    held = int(m["num_experts"])
    published = next((int(c.get("published", {})[k]) for k in EXPERT_KEYS
                      if k in c.get("published", {})), held)
    return (held, published, int(m["num_experts_per_tok"]),
            int(m.get("moe_d_ff") or c["intermediate_size"]))


def _shared_width(c: dict) -> int:
    m = _model(c)
    n = int(m.get("num_shared_experts", 0))
    if not n:
        return 0
    return int(m.get("shared_expert_d_ff") or n * _experts(c)[3])


def layer_linears(c: dict, kind: str = "attn") -> list[tuple[str, int, int]]:
    """(name, m, k) of the linears of one block of ``kind`` that run as
    linear kernels (m outputs, k inputs); a moe block's routed experts
    and router are not among them."""
    d = c["hidden_size"]
    h, hk, dh = _heads(c)
    out = [("wq", h * dh, d), ("wk", hk * dh, d), ("wv", hk * dh, d),
           ("wo", d, h * dh)]
    if kind in ("attn", "local"):
        return out + _mlp(c, "", c["intermediate_size"])
    if kind == "moe":
        sff = _shared_width(c)
        return out + (_mlp(c, "shared_", sff) if sff else [])
    raise ValueError(f"no work counts for block kind {kind!r}")


def head_linear(c: dict) -> tuple[str, int, int]:
    return ("lm_head", c["vocab_size"], c["hidden_size"])


def layer_params(c: dict, kind: str):
    """Weights one token is multiplied by in a block of ``kind``: its
    linears, and for a moe block the router and the expected share of
    routed experts."""
    n = sum(m * k for _, m, k in layer_linears(c, kind))
    if kind != "moe":
        return n
    held, published, per_tok, eff = _experts(c)
    routed = sum(m * k for _, m, k in _mlp(c, "", eff))
    return n + c["hidden_size"] * held + per_tok * held / published * routed


def linear_params(c: dict):
    """Weights of every linear a token's step runs, the LM head included
    (for expert layers, their expected share)."""
    per_kind = {k: layer_params(c, k) for k in set(layer_kinds(c))}
    _, m, k = head_linear(c)
    return sum(per_kind[kind] for kind in layer_kinds(c)) + m * k


def linear_bytes(c: dict, m: int, k: int, b: int) -> float:
    sb = c["program"]["quant"]["scale_block"]
    scales = m * -(-k // sb) * c["program"]["scale_bytes"]
    return m * k / 2 + scales + ACT_BYTES * b * (k + m)


def least_time_s(c: dict, m: int, k: int, b: int, pk: dict) -> float:
    """Least time of one linear call over ``b`` useful rows (0 rows: no
    work is needed)."""
    if b <= 0:
        return 0.0
    return max(2.0 * m * k * b / pk["bf16_flops_per_s"],
               linear_bytes(c, m, k, b) / pk["hbm_bytes_per_s"])


def step_linear_least_s(c: dict, rows: int, head_rows: int,
                        pk: dict, chips: int = 1) -> float:
    """Least time of every linear call of one step on ``chips`` chips
    together: ``rows`` through each layer's linears, ``head_rows``
    through the LM head."""
    kinds = layer_kinds(c)
    t = 0.0
    for kind in sorted(set(kinds)):
        per = sum(least_time_s(c, m, k, rows, pk)
                  for _, m, k in layer_linears(c, kind))
        t += per * kinds.count(kind)
    _, m, k = head_linear(c)
    return (t + least_time_s(c, m, k, head_rows, pk)) / chips


def model_flops(c: dict, tokens: int, ctx: int,
                ctx_local: int | None = None) -> float:
    """Model operations for ``tokens`` processed, whose attended context
    positions sum to ``ctx`` (``ctx_local`` within a local layer's
    window): 2 per linear weight per token, and 4 per head dimension per
    attended position for QK and PV, in every layer."""
    h, _, dh = _heads(c)
    kinds = layer_kinds(c)
    n_local = kinds.count("local")
    flops = (2.0 * linear_params(c) * tokens
             + 4.0 * (len(kinds) - n_local) * h * dh * ctx)
    if n_local:
        if ctx_local is None:
            raise ValueError("local layers need the window's context sum")
        flops += 4.0 * n_local * h * dh * ctx_local
    return flops
