"""Analytic per-GeMM cost model: flops / bytes / attainable time.

``benchmarks/roofline.py`` prices *whole model steps* against a pod;
this module prices *one kernel invocation* on *this process's device*
so kernel profiling hooks (``kernels/ops``) and the microbench can
annotate every measured wall time with an achieved-vs-attainable
fraction.  Conventions match the roofline module (1 MAC = 2 FLOPs;
LUT-consume table adds = 1 op each, retired on the vector unit on
current TPUs — the paper §6 limiting factor).

The peak table is keyed by ``jax.Device.device_kind``, each row with its
source.  A device kind that is not in the table is an error: a chip is
never priced with another device's peaks.  The cpu row is deliberately
rough — on CPU the "fraction" column only compares shapes with each
other — and the microbench records which row priced each result.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass


@dataclass(frozen=True)
class Device:
    name: str
    matmul_flops: float   # peak dense-matmul FLOP/s (MXU / tensor core)
    vector_flops: float   # peak vector-unit op rate (LUT consume adds)
    mem_bw: float         # B/s main-memory bandwidth

    def as_dict(self) -> dict:
        return asdict(self)


DEVICES = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM.  The vector-unit rate is not published; 4e12 op/s is the
    # estimate benchmarks/roofline.Hardware uses.
    "TPU v5 lite": Device("TPU v5e", 197e12, 4e12, 819e9),
    # rough host numbers, one AVX2 socket-ish: shape-to-shape only
    "cpu": Device("cpu-host", 1e11, 5e10, 3e10),
}


def device(kind: str | None = None) -> Device:
    """The peak row for a ``device_kind`` (default: this process's first
    device).  Raises KeyError for a kind without a row."""
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    try:
        return DEVICES[kind]
    except KeyError:
        raise KeyError(
            f"no peak numbers for device kind {kind!r}; add a row with "
            f"its source to repro.obs.costs.DEVICES") from None


def produce_table_ops(d: int) -> float:
    """Eq.-9 op count to build ONE d-digit LUT column (16^d entries)
    from one d-wide activation chunk.

    The table is built hierarchically: every i-digit prefix table is
    shared by all 16^(d-i) extensions, so level i costs 16^i adds and
    the whole build costs sum_{i=1..d} 16^i ~= 16^d * 16/15 — NOT
    16^d * d.  (The previous formula priced every entry as d
    independent multiply-adds, overcounting produce work — and the
    matching transient LUT traffic — by a factor that grows linearly
    in d; the overcount is what made d > 2 look produce-bound.)
    """
    return float(sum(16 ** i for i in range(1, d + 1)))


def lut_bytes(k: int, b: int, d: int = 3) -> float:
    """Transient LUT write+read traffic for one (k, b) produce phase,
    priced at HBM rates: 16^d entries per d-wide chunk, k/d chunks, b
    columns, f32.  The fused Pallas deployment keeps these tiles in
    VMEM (paper §4), so :func:`gemm_cost` reports this separately
    instead of folding it into ``bytes``."""
    return 2 * 16 ** d * (k / d) * b * 4.0


def gemm_cost(m: int, k: int, b: int, *, quant: str = "msgemm",
              d: int = 3, dtype_bytes: float = 2.0) -> dict:
    """Cost of one (b, k) x (k, m) GeMM invocation.

    Returns produce/consume op counts (paper Eq. 9 accounting — the
    shared-prefix table build, see :func:`produce_table_ops`), bytes
    moved through main memory, and the arithmetic totals the roofline
    fraction divides by.  ``quant`` other than msgemm prices the dense
    path (produce = the whole matmul, consume = 0).  ``lut_bytes`` is
    the transient LUT spill traffic for deployments whose LUT does NOT
    stay in VMEM; it is reported but excluded from ``bytes`` (the fused
    kernels never move it through HBM).
    """
    if quant == "msgemm":
        # Eq. 9: shared tuple-table build per d-wide chunk (adds +
        # 16 b(i)*x products per digit, the latter negligible)
        produce = 2.0 * produce_table_ops(d) * (k / d) * b
        consume = float(m) * (k / d) * b       # table adds (VPU)
        weight_bytes = (32 / d) / 8 * m * k    # packed digit indices
        lutb = lut_bytes(k, b, d)
    else:
        produce = 2.0 * m * k * b
        consume = 0.0
        weight_bytes = dtype_bytes * m * k
        lutb = 0.0
    act_bytes = dtype_bytes * b * k
    out_bytes = dtype_bytes * b * m
    return {
        "m": m, "k": k, "b": b, "quant": quant, "d": d,
        "produce_flops": produce,
        "consume_ops": consume,
        "flops": produce + consume,
        "bytes": weight_bytes + act_bytes + out_bytes,
        "weight_bytes": weight_bytes,
        "lut_bytes": lutb,
    }


def attainable_s(cost: dict, dev: Device | None = None) -> float:
    """Roofline lower bound for one invocation: max of the compute term
    (produce at matmul rate + consume at vector rate) and the memory
    term."""
    dev = dev or device()
    compute = (cost["produce_flops"] / dev.matmul_flops
               + cost["consume_ops"] / dev.vector_flops)
    memory = cost["bytes"] / dev.mem_bw
    return max(compute, memory)


def achieved_fraction(measured_s: float, cost: dict,
                      dev: Device | None = None) -> float:
    """attainable / measured — 1.0 means running at the roofline, small
    means leaving performance on the table.  0.0 when measured time is
    degenerate."""
    if measured_s <= 0.0:
        return 0.0
    return attainable_s(cost, dev) / measured_s


def annotate(measured_s: float, m: int, k: int, b: int, *,
             quant: str = "msgemm", d: int = 3,
             dev: Device | None = None) -> dict:
    """One-call convenience for benchmark rows: cost + attainable +
    fraction + the hardware model that priced it."""
    dev = dev or device()
    cost = gemm_cost(m, k, b, quant=quant, d=d)
    att = attainable_s(cost, dev)
    return {
        **cost,
        "measured_s": measured_s,
        "attainable_s": att,
        "roofline_fraction": att / measured_s if measured_s > 0 else 0.0,
        "hardware": dev.name,
    }
