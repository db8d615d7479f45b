"""Tiny configurations of the block kinds a configuration file can ask
for in ``program.model``: a window/full alternation and an expert layer
with a shared expert.  Each is a tiny configuration of the benchmark
(``bench.rehearse.tiny_config``) with that group added, and no other
change."""

from __future__ import annotations

# 4 experts, 2 per token: a capacity factor of E / K drops no token, as
# the published models (and the reference) do
VARIANTS = {
    "local": {"block_pattern": ["local", "attn"], "sliding_window": 8},
    "moe": {"family": "moe", "block_pattern": ["moe"], "num_experts": 4,
            "num_experts_per_tok": 2, "moe_d_ff": 36,
            "num_shared_experts": 1, "shared_expert_d_ff": 72,
            "capacity_factor": 2.0},
}


def variant(c: dict, name: str) -> dict:
    """Configuration ``c`` with the ``program.model`` group ``name``."""
    return dict(c, program=dict(c["program"], model=VARIANTS[name]))
