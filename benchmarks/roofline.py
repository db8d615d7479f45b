"""Roofline analysis (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape x quant) cell on the single-pod mesh:

    compute    = EXEC_FLOPS   / (chips x 197e12 FLOP/s)
    memory     = HBM_BYTES    / (chips x 819e9  B/s)
    collective = COLL_BYTES   / (chips x 50e9   B/s per ICI link)

EXEC_FLOPS / HBM_BYTES / COLL_BYTES come from an *analytic per-block cost
model* mirroring the model code exactly (scan bodies make XLA's
cost_analysis count loop bodies once, so raw HLO numbers undercount; the
dry-run JSON is used as the memory-fit proof + a collective-op inventory
cross-check, and §Dry-run spot-checks the analytic FLOPs against a
1-vs-2-group lowering extrapolation).

Conventions: 1 MAC = 2 FLOPs; LUT-consume adds = 1 FLOP (paper §4 counts
them as table adds — this is the instruction-count the paper optimizes).
MODEL_FLOPS = 6·N_active·tokens (train) or 2·N_active·tokens (serve) —
the "useful" flops; EXEC/MODEL ratio exposes remat + produce-phase +
dispatch overheads.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass

from repro import configs
from repro.configs import shapes as shp
from repro.models.config import ModelConfig, param_count

RESULTS = os.path.join(os.path.dirname(__file__), "results", "dryrun")


@dataclass(frozen=True)
class Hardware:
    name: str = "tpu-v5e-class"
    peak_flops: float = 197e12  # bf16 MXU / chip
    vpu_flops: float = 4e12  # vector-unit gather/add rate (~2% of MXU —
    # the TPU analogue of the paper's 19.5-vs-312 TFLOPS CUDA/Tensor split)
    hbm_bw: float = 819e9  # B/s
    ici_bw: float = 50e9  # B/s per link
    vmem_bytes: int = 16 * 2**20  # per-core working set for LUT tiles


HW = Hardware()
CHIPS = 256  # single-pod roofline mesh (16 x 16)
MESH = {"data": 16, "model": 16}


# ---------------------------------------------------------------------------
# per-component FLOPs (forward, per token unless noted); 1 MAC = 2 FLOPs
# ---------------------------------------------------------------------------
def linear_flops(k: int, m: int, quant: str, d: int = 3,
                 split: bool = False):
    """One (k->m) linear, per token.  With split=True returns
    (mxu_flops, vpu_ops): the consume-phase table adds execute on the
    vector unit on current TPUs (paper §6's limiting factor).

    quant='msgemm_adaptive' picks the best depth per linear (beyond-paper:
    d* = argmax_d Eq. 15 for this (m, k), bounded to [1, 4]) instead of a
    model-wide d — small-m projections drop to d=2 where 16^d
    amortizes, the lm_head keeps d=3/4."""
    if quant == "msgemm_adaptive":
        from repro.core import complexity as C

        d = max(2, C.best_d(m, k, range(2, 5))[0])
        quant = "msgemm"
    if quant == "msgemm" and m >= 16**d / 4:
        from repro.obs import costs as _costs

        # Eq. 9 shared-prefix table build (sum_{i<=d} 16^i per chunk,
        # k/d chunks) — see obs.costs.produce_table_ops; the old
        # 2*16^d*k form overcounted produce linearly in d
        produce = 2.0 * _costs.produce_table_ops(d) * (k / d)
        consume = m * (k / d)  # table adds (paper Eq. 9)
        return (produce, consume) if split else produce + consume
    # dense / int4_dequant / msgemm-with-tiny-m (expert policy: falls back
    # to the dequant path, DESIGN.md §5)
    f = 2.0 * m * k
    return (f, 0.0) if split else f


def linear_weight_bytes(k: int, m: int, quant: str, d: int = 3,
                        storage: str = "packed_idx") -> float:
    if quant == "bf16":
        return 2.0 * m * k
    if quant == "int4_dequant":
        return 0.5 * m * k + 4.0 * m * (k / 36)  # packed u8 + scales
    bits = 32 / d if storage == "packed_idx" else 4  # msgemm layouts
    return bits / 8 * m * k + 4.0 * m * (k / 36)


def lut_bytes(k: int, b: int, d: int = 3) -> float:
    """Transient LUT write+read traffic per linear for a b-column GeMM —
    the §4 'kept in cache' assumption, priced at HBM rates when it
    doesn't fit VMEM (obs.costs.lut_bytes is the shared formula)."""
    from repro.obs import costs as _costs

    return _costs.lut_bytes(k, b, d)


def _block_linears(cfg: ModelConfig, kind: str):
    """(k, m) of every QuantizedLinear in one block + dense (non-quant)
    matmul flops per token."""
    d, dff = cfg.d_model, cfg.d_ff
    h, hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    gated = cfg.mlp_activation in ("swiglu", "geglu")
    lin = []
    dense = 0.0
    mdff = cfg.moe_d_ff or dff

    def mlp(ff):
        lin.extend([(d, ff)] * (2 if gated else 1) + [(ff, d)])

    if kind in ("attn", "local", "moe"):
        lin += [(d, h * dh), (d, hk * dh), (d, hk * dh), (h * dh, d)]
        if kind == "moe":
            dense += 2.0 * d * cfg.num_experts  # router
            for _ in range(cfg.num_experts_per_tok):
                lin.extend([(d, mdff)] * (2 if gated else 1) + [(mdff, d)])
            if cfg.num_shared_experts:
                mlp(cfg.shared_expert_d_ff or cfg.num_shared_experts * mdff)
        else:
            mlp(dff)
    elif kind in ("mamba", "mamba_moe"):
        di, n, dr = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.dt_rank
        lin += [(d, 2 * di), (di, dr + 2 * n), (di, d)]
        dense += 2.0 * dr * di + 2 * cfg.mamba_d_conv * di + 10.0 * di * n
        if kind == "mamba_moe":
            dense += 2.0 * d * cfg.num_experts
            for _ in range(cfg.num_experts_per_tok):
                lin.extend([(d, mdff)] * (2 if gated else 1) + [(mdff, d)])
        else:
            mlp(dff)
    elif kind == "mlstm":
        di = int(d * cfg.xlstm_proj_factor)
        dh_ = di // cfg.num_heads
        lin += [(d, 2 * di), (d, di), (di, d)]
        dense += (3 * 2.0 * di * dh_  # block-diag qkv
                  + 2 * cfg.xlstm_conv * di + 2.0 * 2 * cfg.num_heads * di
                  + 8.0 * cfg.num_heads * dh_ * dh_)  # recurrence C/n/read
    elif kind == "slstm":
        mf = int(d * cfg.slstm_mlp_factor)
        dense += 8.0 * d * d + 12.0 * d  # 4 gates W+R + pointwise
        lin.extend([(d, mf), (d, mf), (mf, d)])
    return lin, dense


def attn_mix_flops(cfg: ModelConfig, kind: str, s_q: float, s_kv: float,
                   causal: bool = True) -> float:
    """Sequence-mixing flops per *query token* for one attention block."""
    if kind == "local" and cfg.sliding_window:
        s_kv = min(s_kv, cfg.sliding_window)
    elif causal:
        s_kv = s_kv / 2  # average causal visibility
    return 4.0 * s_kv * cfg.num_heads * cfg.head_dim  # QK^T + PV


def forward_flops_per_token(cfg: ModelConfig, quant: str, d: int,
                            s_q: float, s_kv: float, causal=True,
                            decode=False) -> tuple[float, float]:
    """One full forward, per token -> (mxu_flops, vpu_consume_ops)."""
    mxu = 0.0
    vpu = 0.0
    reps = cfg.num_groups
    for kind in cfg.block_pattern:
        lin, dense = _block_linears(cfg, kind)
        # expert linears run int4_dequant under msgemm (policy): the small-m
        # guard inside linear_flops handles that automatically.
        for k, m in lin:
            f, c = linear_flops(k, m, quant, d, split=True)
            mxu += reps * f
            vpu += reps * c
        mxu += reps * dense
        if kind in ("attn", "local", "moe"):
            mxu += reps * attn_mix_flops(cfg, kind, s_q, s_kv, causal)
    # lm head
    f, c = linear_flops(cfg.d_model, cfg.vocab_size,
                        quant if not cfg.tie_embeddings else "bf16", d,
                        split=True)
    mxu += f
    vpu += c
    if cfg.is_encdec:  # decoder cross-attention reads the encoder output
        mxu += cfg.num_layers * (
            2.0 * cfg.d_model * cfg.num_heads * cfg.head_dim  # q proj
            + 4.0 * s_kv * cfg.num_heads * cfg.head_dim)  # s_kv = frames
    return mxu, vpu


def weight_bytes_total(cfg: ModelConfig, quant: str, d: int,
                       active_only: bool) -> float:
    """Bytes of weights touched by one forward (per step, not per token)."""
    reps = cfg.num_groups
    total = 0.0
    for kind in cfg.block_pattern:
        lin, _ = _block_linears(cfg, kind)
        if kind in ("moe", "mamba_moe") and not active_only:
            # all experts resident; active_only counts routed ones (done
            # in _block_linears already via num_experts_per_tok)
            mdff = cfg.moe_d_ff or cfg.d_ff
            gated = cfg.mlp_activation in ("swiglu", "geglu")
            extra = cfg.num_experts - cfg.num_experts_per_tok
            lin = lin + ([(cfg.d_model, mdff)] * (2 if gated else 1)
                         + [(mdff, cfg.d_model)]) * extra
        total += reps * sum(linear_weight_bytes(k, m, quant, d)
                            for k, m in lin)
    total += 2.0 * cfg.vocab_size * cfg.d_model  # embeddings bf16
    if not cfg.tie_embeddings:
        total += linear_weight_bytes(cfg.d_model, cfg.vocab_size, quant, d)
    if cfg.is_encdec:
        _, _ = 0, 0  # encoder linears ~ decoder-sized; approximate below
        total *= (cfg.num_layers + cfg.encoder_layers) / cfg.num_layers
    return total


# ---------------------------------------------------------------------------
# per-cell roofline
# ---------------------------------------------------------------------------
def cell_terms(arch: str, shape_name: str, quant: str = "auto",
               d: int = 3, storage: str = "packed_idx",
               chips: int = CHIPS, mesh=None,
               lut_in_vmem: bool = True,
               lut_add_unit: bool = False,
               kv_bytes_per_elem: float = 2.0) -> dict:
    """Analytic three-term roofline for one cell.

    lut_in_vmem:  True = fused Pallas-kernel deployment (LUT tiles never
                  touch HBM — the paper's §4 'kept in cache' assumption,
                  realizable since 16^d x TJ x TB_64 x 4B < 16 MB VMEM);
                  False = the XLA-lowered jnp fallback that spills LUT
                  slabs to HBM (what the at-scale dry-run compiles).
    lut_add_unit: True = the paper's §6 proposed hardware (LUT adds at
                  MXU rate); False = current TPU (consume on the VPU).
    """
    cfg = configs.get_config(arch)
    shape = shp.SHAPES[shape_name]
    mesh = mesh or MESH
    ok, reason = shp.applicable(cfg, shape_name)
    if not ok:
        return {"cell": f"{arch}/{shape_name}", "skipped": reason}
    if quant == "auto":
        quant = "bf16" if shape.kind == "train" else "msgemm"

    B, S = shape.global_batch, shape.seq_len
    pc = param_count(cfg)
    n_active, n_total = pc["active"], pc["total"]

    def lut_traffic(tokens_per_chip: float) -> float:
        if quant != "msgemm" or lut_in_vmem:
            return 0.0
        per_tok = sum(cfg.num_groups * sum(
            lut_bytes(k, 1, d) for k, m in _block_linears(cfg, kind)[0]
            if m >= 16**d / 4)  # expert policy: small-m uses dequant
            for kind in cfg.block_pattern)
        return per_tok * tokens_per_chip * chips

    def encdec_split(total_tokens: float, s_src: float):
        """Whisper: seq_len drives the ENCODER (s_src frames); the decoder
        sees <=448 tokens.  Returns (enc_tok, dec_tok, enc_fwd_per_tok,
        enc_params)."""
        if not cfg.is_encdec:
            return 0.0, total_tokens, 0.0, 0.0
        dec_tok = B * min(cfg.max_seq_len, 448)
        lin, _ = _block_linears(cfg, "attn")
        per_layer = sum(2.0 * mm * kk for kk, mm in lin)
        enc_fwd = cfg.encoder_layers * (
            per_layer + 4.0 * s_src * cfg.num_heads * cfg.head_dim)
        enc_params = cfg.encoder_layers * sum(kk * mm for kk, mm in lin)
        return total_tokens, dec_tok, enc_fwd, enc_params

    if shape.kind == "train":
        tokens = B * S
        enc_tok, dec_tok, enc_fwd, enc_params = encdec_split(tokens, S)
        mxu, vpu = forward_flops_per_token(cfg, "bf16", d, S, S)
        mxu_total = (dec_tok * mxu + enc_tok * enc_fwd) * 4.0  # +bwd+remat
        vpu_total = 0.0
        model_flops = 6.0 * ((n_active - enc_params) * dec_tok
                             + enc_params * enc_tok) if cfg.is_encdec \
            else 6.0 * n_active * tokens
        wb = 2.0 * n_total  # bf16 weights
        hbm = tokens * cfg.d_model * 2 * 2 * cfg.num_layers * 2  # acts r/w
        hbm += 8 * wb  # fwd + bwd + grads + adam read/write passes
        # collectives: FSDP all-gather (fwd + bwd re-gather) + grad RS.
        # Expert weights with E | model are EP-full-sharded (expert x
        # data) — no FSDP gather; tokens move via all-to-all instead
        # (§Perf A, confirmed in the lowered HLO).
        fsdp_params = 2.0 * n_total
        a2a = 0.0
        if cfg.num_experts and cfg.num_experts % mesh["model"] == 0:
            expert_frac = 1.0 - param_count(
                cfg.replace(num_experts=0, num_experts_per_tok=0,
                            block_pattern=tuple(
                                "attn" if k in ("moe",) else
                                ("mamba" if k == "mamba_moe" else k)
                                for k in cfg.block_pattern)))["total"] / n_total
            fsdp_params *= (1.0 - expert_frac)
            moe_layers = sum(k in ("moe", "mamba_moe")
                             for k in cfg.block_pattern) * cfg.num_groups
            # dispatch + combine, fwd + bwd, f32 dispatch buffers
            a2a = moe_layers * (tokens / chips) * cfg.d_model * 4 * 2 * 2
        p_shard = fsdp_params / chips
        coll = 3 * p_shard * (mesh["data"] - 1) + a2a
        coll += (2 * cfg.num_layers * 2.0 * tokens * cfg.d_model
                 / chips) * 2 * (mesh["model"] - 1) / mesh["model"]
    elif shape.kind == "prefill":
        tokens = B * S
        enc_tok, dec_tok, enc_fwd, enc_params = encdec_split(tokens, S)
        mxu, vpu = forward_flops_per_token(cfg, quant, d, S, S)
        mxu_total = dec_tok * mxu + enc_tok * enc_fwd
        vpu_total = dec_tok * vpu
        model_flops = 2.0 * ((n_active - enc_params) * dec_tok
                             + enc_params * enc_tok) if cfg.is_encdec \
            else 2.0 * n_active * tokens
        wb = weight_bytes_total(cfg, quant, d, active_only=False)
        hbm = wb + tokens * cfg.d_model * 2 * 2 * cfg.num_layers
        hbm += lut_traffic(tokens / chips)
        coll = (2 * cfg.num_layers * 2.0 * tokens * cfg.d_model / chips
                ) * 2 * (mesh["model"] - 1) / mesh["model"]
        coll += 2.0 * tokens * cfg.vocab_size / chips / mesh["model"]
    else:  # decode: one token per sequence
        tokens = B
        _, _, _, enc_params = encdec_split(tokens, S)
        mxu, vpu = forward_flops_per_token(cfg, quant, d, 1, S,
                                           causal=False, decode=True)
        mxu_total, vpu_total = tokens * mxu, tokens * vpu
        model_flops = 2.0 * (n_active - enc_params) * tokens  # decoder only
        wb = weight_bytes_total(cfg, quant, d, active_only=False)
        hbm = wb  # every resident weight read once per decode step
        kv = 0.0
        for kind in cfg.block_pattern:
            if kind in ("attn", "local", "moe"):
                s_vis = min(S, cfg.sliding_window) if (
                    kind == "local" and cfg.sliding_window) else S
                kv += cfg.num_groups * B * s_vis
        hbm += (kv * cfg.num_kv_heads * cfg.head_dim * 2
                * kv_bytes_per_elem)  # k+v read (bf16 default; f8 = 1)
        for kind in cfg.block_pattern:  # recurrent state caches
            if kind in ("mamba", "mamba_moe"):
                hbm += (cfg.num_groups * B * cfg.mamba_d_inner
                        * cfg.mamba_d_state * 4 * 2)
            if kind == "mlstm":
                di = int(cfg.d_model * cfg.xlstm_proj_factor)
                dh_ = di // cfg.num_heads
                hbm += cfg.num_groups * B * cfg.num_heads * dh_ * dh_ * 4 * 2
        hbm += lut_traffic(max(tokens / chips, 1.0))
        coll = (2 * cfg.num_layers * 2.0 * tokens * cfg.d_model / chips
                ) * 2 * (mesh["model"] - 1) / mesh["model"]

    # a LUT-add unit retires one table-add per FMA slot (peak/2 adds/s)
    consume_rate = HW.peak_flops / 2 if lut_add_unit else HW.vpu_flops
    terms = {
        "compute_s": (mxu_total / (chips * HW.peak_flops)
                      + vpu_total / (chips * consume_rate)),
        "memory_s": hbm / (chips * HW.hbm_bw),
        "collective_s": coll / HW.ici_bw,  # coll is already per device
    }
    dominant = max(terms, key=terms.get)
    bound_s = terms[dominant]
    exec_flops = mxu_total + vpu_total
    return {
        "cell": f"{arch}/{shape_name}/{quant}",
        "arch": arch, "shape": shape_name, "quant": quant,
        "exec_flops": exec_flops, "mxu_flops": mxu_total,
        "consume_ops": vpu_total, "model_flops": model_flops,
        "hbm_bytes": hbm, "collective_bytes_per_dev": coll,
        "lut_in_vmem": lut_in_vmem, "lut_add_unit": lut_add_unit,
        "terms": terms, "dominant": dominant.replace("_s", ""),
        "step_time_bound_s": bound_s,
        "model_over_exec": model_flops / exec_flops,
        "roofline_fraction": (model_flops / (chips * HW.peak_flops))
        / bound_s if bound_s else 0.0,
    }


# ---------------------------------------------------------------------------
# kernel-level roofline (obs.costs bridge)
# ---------------------------------------------------------------------------
# The cells above price whole model steps on a pod; these wrappers price
# ONE kernel invocation on THIS process's device, so microbenchmarks and
# the kernels/ops.profile_gemm hook can annotate every measured wall
# time with an achieved-vs-attainable fraction.  The arithmetic model is
# shared with src/repro/obs/costs.py (same Eq. 9 produce/consume split).

def kernel_cost(m: int, k: int, b: int, quant: str = "msgemm",
                d: int = 3) -> dict:
    """Per-invocation flops/bytes (obs.costs.gemm_cost re-export)."""
    from repro.obs import costs

    return costs.gemm_cost(m, k, b, quant=quant, d=d)


def kernel_attainable_s(m: int, k: int, b: int, quant: str = "msgemm",
                        d: int = 3, kind: str | None = None) -> float:
    """Roofline lower bound for one (b,k)x(k,m) call on the current (or
    named) device kind's peaks (obs.costs.DEVICES)."""
    from repro.obs import costs

    return costs.attainable_s(costs.gemm_cost(m, k, b, quant=quant, d=d),
                              costs.device(kind))


def kernel_fraction(measured_s: float, m: int, k: int, b: int,
                    quant: str = "msgemm", d: int = 3,
                    kind: str | None = None) -> float:
    """attainable / measured for one invocation (1.0 = at the roofline)."""
    from repro.obs import costs

    return costs.achieved_fraction(
        measured_s, costs.gemm_cost(m, k, b, quant=quant, d=d),
        costs.device(kind))


def kernel_report(bench_path: str | None = None,
                  calibration_path: str | None = None) -> list[dict]:
    """Per-shape measured-vs-attainable report from BENCH_kernels.json.

    One row per (shape, grid) with the measured kernel time, the
    roofline-attainable time for the device the bench ran on
    (obs.costs), the achieved fraction of that bound, and — when a
    perf-model calibration matching the bench's (device, interpret)
    partition is available — the calibrated model's predicted wall time
    and the measured/predicted ratio (the same ratio the regression
    sentinel gates on)."""
    from repro.obs import costs, perfmodel as pm

    bench_path = bench_path or os.path.join(
        os.path.dirname(__file__), "results", "BENCH_kernels.json")
    try:
        with open(bench_path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return []
    dev_name = doc.get("device", "cpu")
    dev = costs.device(doc.get("device_kind", dev_name))
    interpret = bool(doc.get("interpret", dev_name != "tpu"))
    calib = pm.load_calibration(calibration_path, device=dev_name,
                                interpret=interpret)
    rows = []
    for s in pm.samples_from_bench(bench_path):
        attain = costs.attainable_s(
            costs.gemm_cost(s.m, s.k, s.b, quant="msgemm", d=s.d), dev)
        row = {
            "source": s.source, "backend": s.backend,
            "m": s.m, "k": s.k, "b": s.b, "d": s.d,
            "grid": "vmem-acc" if s.acc_in_vmem else "legacy",
            "measured_s": s.measured_s,
            "attainable_s": attain,
            "attainable_fraction": attain / s.measured_s,
            "device": dev_name, "interpret": interpret,
        }
        if calib is not None:
            pred = pm.predict_sample(s, calib).t_total_s
            row["predicted_s"] = pred
            row["measured_over_predicted"] = s.measured_s / max(pred, 1e-12)
        rows.append(row)
    return rows


def render_kernel_markdown(rows: list[dict]) -> str:
    if not rows:
        return ("(no BENCH_kernels.json — run "
                "benchmarks/kernel_microbench.py first)")
    calibrated = any("predicted_s" in r for r in rows)
    hdr = "| shape | grid | measured | attainable | % of peak |"
    sep = "|---|---|---|---|---|"
    if calibrated:
        hdr += " model pred | meas/pred |"
        sep += "---|---|"
    out = [f"device={rows[0]['device']} interpret={rows[0]['interpret']} "
           f"(interpret-mode fractions are orders below hardware peak "
           f"by construction)", "", hdr, sep]
    for r in rows:
        line = (f"| m{r['m']} k{r['k']} b{r['b']} d{r['d']} | {r['grid']} "
                f"| {r['measured_s']:.3e}s | {r['attainable_s']:.3e}s | "
                f"{100 * r['attainable_fraction']:.2f}% |")
        if calibrated:
            if "predicted_s" in r:
                line += (f" {r['predicted_s']:.3e}s | "
                         f"{r['measured_over_predicted']:.2f}x |")
            else:
                line += " — | — |"
        out.append(line)
    return "\n".join(out)


def load_dryrun(arch: str, shape: str, mesh: str = "single",
                quant: str = "auto") -> dict | None:
    if quant == "auto":
        quant = "bf16" if shape == "train_4k" else "msgemm"
    p = os.path.join(RESULTS, f"{arch}__{shape}__{mesh}__{quant}.json")
    if not os.path.exists(p):
        return None
    with open(p) as f:
        return json.load(f)


def full_table(quant: str = "auto") -> list[dict]:
    rows = []
    for arch in configs.ARCHS:
        for shape in shp.SHAPES:
            r = cell_terms(arch, shape, quant)
            dr = load_dryrun(arch, shape)
            if dr and dr.get("status") == "ok":
                r["mem_per_dev_gb"] = dr["memory"]["total_per_device_gb"]
                r["hlo_collectives"] = {
                    k: v["count"] for k, v in dr["collectives"].items()
                    if v["count"]}
                r["compile_s"] = dr["compile_s"]
            rows.append(r)
    return rows


def render_markdown(rows: list[dict]) -> str:
    out = ["| cell | dominant | compute s | memory s | collective s | "
           "MODEL/EXEC | roofline frac | mem/dev GB |",
           "|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if "skipped" in r:
            out.append(f"| {r['cell']} | SKIP | — | — | — | — | — | — |")
            continue
        t = r["terms"]
        out.append(
            f"| {r['cell']} | **{r['dominant']}** | {t['compute_s']:.4f} | "
            f"{t['memory_s']:.4f} | {t['collective_s']:.4f} | "
            f"{r['model_over_exec']:.2f} | {r['roofline_fraction']:.2f} | "
            f"{r.get('mem_per_dev_gb', float('nan')):.1f} |")
    return "\n".join(out)


def main():
    res = os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(res, exist_ok=True)
    rows = full_table()
    md = render_markdown(rows)
    with open(os.path.join(res, "roofline.md"), "w") as f:
        f.write(md + "\n")
    with open(os.path.join(res, "roofline.json"), "w") as f:
        json.dump(rows, f, indent=1, default=float)
    print(md)
    krows = kernel_report()
    if krows:
        kmd = render_kernel_markdown(krows)
        with open(os.path.join(res, "roofline_kernels.md"), "w") as f:
            f.write(kmd + "\n")
        with open(os.path.join(res, "roofline_kernels.json"), "w") as f:
            json.dump(krows, f, indent=1, default=float)
        print()
        print(kmd)


if __name__ == "__main__":
    main()
