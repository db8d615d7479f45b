"""The work a step needs, from the configuration's shapes alone.

These counts do not depend on how the program computes: a linear of
(m, k) over b useful rows needs 2*m*k*b operations and must read its
4-bit codes (m*k/2 bytes), its scales (one per ``scale_block`` weights
of a row) and its bf16 activations, and write its bf16 outputs.  Its
least time on a chip is the larger of operations over the chip's bf16
peak and bytes over its HBM bandwidth (``peaks.json``).
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
ACT_BYTES = 2  # bf16 activations in and out


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def layer_linears(c: dict) -> list[tuple[str, int, int]]:
    """(name, m, k) of one dense block's linears (m outputs, k inputs)."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    out = [("wq", h * dh, d), ("wk", hk * dh, d), ("wv", hk * dh, d),
           ("wo", d, h * dh), ("up", ff, d), ("down", d, ff)]
    if c["program"]["mlp_activation"] in ("swiglu", "geglu"):
        out.append(("gate", ff, d))
    return out


def head_linear(c: dict) -> tuple[str, int, int]:
    return ("lm_head", c["vocab_size"], c["hidden_size"])


def linear_params(c: dict) -> int:
    """Weights of every linear the step runs, the LM head included."""
    per_layer = sum(m * k for _, m, k in layer_linears(c))
    _, m, k = head_linear(c)
    return per_layer * c["num_hidden_layers"] + m * k


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
                        pk: dict) -> float:
    """Least time of every linear call of one step: ``rows`` through
    each layer's linears, ``head_rows`` through the LM head."""
    t = sum(least_time_s(c, m, k, rows, pk) for _, m, k in layer_linears(c))
    _, m, k = head_linear(c)
    return (t * c["num_hidden_layers"]
            + least_time_s(c, m, k, head_rows, pk))


def model_flops(c: dict, tokens: int, ctx: int) -> float:
    """Model operations for ``tokens`` processed, whose attended context
    positions sum to ``ctx``: 2 per linear weight per token, and 4 per
    head dimension per attended position for QK and PV, in every layer."""
    h = c["num_attention_heads"]
    dh = c.get("head_dim") or c["hidden_size"] // h
    return (2.0 * linear_params(c) * tokens
            + 4.0 * c["num_hidden_layers"] * h * dh * ctx)
