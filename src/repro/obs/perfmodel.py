"""Analytical kernel-time model, calibration, and regression sentinel.

``obs.costs`` prices a GeMM against an idealized roofline; this module
predicts the *wall time of our actual kernels* from a handful of
per-device constants, the way Markidis et al. predict Tensor Core
throughput from measured machine constants:

    t = launch_s
      + step_s            * grid_steps
      + produce_s_per_flop * produce_flops     (Eq.-9 LUT build, incl.
                                                legacy-grid re-production)
      + consume_s_per_op  * (consume_ops + epilogue_ops)
      + hbm_s_per_byte    * hbm_bytes          (incl. jnp LUT spill and
                                                legacy per-step writeback)

The five constants are **calibrated** by weighted least squares from
timings the stack already persists — the autotuner's per-candidate
``timings`` tables in the plan cache, ``BENCH_kernels.json`` rows, and
``kernel_gemm_s`` histograms from a traced serve run — and stored as a
versioned ``calibration.json`` artifact.  The fit minimizes *relative*
error (each row is scaled by 1/measured), so microsecond decode shapes
weigh the same as millisecond prefill shapes.

Calibrations are partitioned on (device, interpret): an interpret-mode
CPU fit is never used to predict compiled TPU kernels and vice versa
(timing rows that predate the ``interpret`` tag are skipped).

Consumers:

* ``dispatch.autotune`` ranks candidate plans by :func:`predict` and
  measures only the predicted-top-few (model-guided search);
* ``python -m repro.obs --check-regressions`` compares every measured
  timing against the model within a tolerance band and fails CI on
  outliers (the regression sentinel);
* ``benchmarks/roofline.py`` reports measured vs model-attainable time
  per shape.

Tolerance band: a measurement is an outlier when
``measured > tolerance * predicted`` (default ``DEFAULT_TOLERANCE`` =
3.0x — generous against interpret-mode jitter, tight enough that a
dropped produce amortization or a 10x-slowed kernel always trips it).
Faster-than-predicted rows are reported (``fast=true``) but never fail:
a kernel beating the model is not a regression.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from dataclasses import dataclass, field
from pathlib import Path

CALIBRATION_VERSION = 1
DEFAULT_TOLERANCE = 3.0

# model constants, in feature-vector order (the fit solves for these)
CONSTANT_NAMES = ("launch_s", "step_s", "produce_s_per_flop",
                  "consume_s_per_op", "hbm_s_per_byte")

# collective-time term (ISSUE 10): predicted extra wall time of a
# pipelined k-sharded linear relative to its one-shot plan,
#   dt = coll_call_s * d(kernel calls) + coll_hop_s * d(hops)
#      + coll_byte_s * d(bytes)
# fitted per (device, interpret) from the plan cache's shard_variants
# timing tables.  Unlike CONSTANT_NAMES these may fit NEGATIVE: a
# negative hop/byte coefficient is the measured overlap benefit — more
# ring hops *reducing* wall time because they hide under compute.  The
# block is additive in calibration.json (version stays 1; files without
# it validate, consumers fall back to measuring every variant).
COLLECTIVE_CONSTANT_NAMES = ("coll_call_s", "coll_hop_s", "coll_byte_s")

# rough per-element op counts for epilogue activations (the epilogue
# term rides the consume rate — it executes on the same vector unit)
_ACT_OPS = {"none": 0.0, "relu": 1.0, "gelu": 8.0, "silu": 6.0}


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def effective_interpret(interpret: bool | None) -> bool:
    """Resolve interpret=None exactly like the kernel wrappers do."""
    from repro.kernels.mode import resolve_interpret

    return resolve_interpret(interpret)


def current_partition() -> tuple[str, bool]:
    """(device, interpret) of this process — the calibration partition
    every fresh measurement in this process belongs to."""
    import jax

    return jax.default_backend(), effective_interpret(None)


# =====================================================================
# samples — one measured kernel invocation, self-describing
# =====================================================================
@dataclass(frozen=True)
class Sample:
    """One measured timing plus everything the model needs to predict
    it.  ``tm/tj/tb`` may be None (heuristic tiles are derived)."""

    backend: str
    mode: str                  # 'msgemm' | 'int4_dequant' | 'bf16'
    d: int
    scale_block: int
    m: int
    k: int
    b: int
    measured_s: float
    device: str
    interpret: bool
    tm: int | None = None
    tj: int | None = None
    tb: int | None = None
    consume_chunk: int = 1
    acc_in_vmem: bool = True
    epilogue_ops: float = 0.0
    source: str = "?"

    def desc(self) -> str:
        return (f"{self.backend} {self.mode} d={self.d} m={self.m} "
                f"k={self.k} b={self.b} tm={self.tm} tj={self.tj} "
                f"tb={self.tb} chunk={self.consume_chunk} "
                f"acc={'vmem' if self.acc_in_vmem else 'legacy'} "
                f"[{self.source}]")


# =====================================================================
# feature extraction — the analytic work terms
# =====================================================================
def features(backend: str, mode: str, d: int, scale_block: int,
             m: int, k: int, b: int, *,
             tm: int | None = None, tj: int | None = None,
             tb: int | None = None, consume_chunk: int = 1,
             acc_in_vmem: bool = True,
             epilogue_ops: float = 0.0) -> dict:
    """The per-invocation work terms, one per model constant.

    Mirrors what the kernels actually execute (padded tile shapes, the
    produce-amortization factor, legacy per-step writeback, the jnp
    backend's HBM-resident LUT) rather than the idealized Eq.-9
    minimum — obs.costs answers "how fast could this be", this answers
    "how long will *our* kernel take".
    """
    from repro.obs import costs

    d = max(int(d), 1)
    sb = max(int(scale_block), d)
    f32 = 4.0
    if backend == "msgemm_pallas" and mode == "msgemm":
        from repro.kernels import ops

        kc = _ceil_div(k, d)
        if tm is None or tj is None or tb is None:
            htm, htj, htb = ops.msgemm_tiles(m, kc, b, d, sb)
            tm, tj, tb = tm or htm, tj or htj, tb or htb
        nm, nj, nb = _ceil_div(m, tm), _ceil_div(kc, tj), _ceil_div(b, tb)
        mp, kcp, bp = nm * tm, nj * tj, nb * tb
        acc = acc_in_vmem and ops.acc_stripe_fits(m, tm, tb)
        steps = nm * nj * nb
        # LUT build per (b, j) tile; the legacy grid re-produces it for
        # every m tile (the PR-4 amortization this model must see to
        # rank acc_in_vmem correctly)
        produce = 2.0 * costs.produce_table_ops(d) * kcp * bp
        if not acc:
            produce *= nm
        consume = float(mp) * kcp * bp
        idx_bytes = f32 * m * kc          # packed digit indices (int32)
        act_bytes = f32 * k * bp          # x read per produce pass
        out_bytes = f32 * mp * bp         # single VMEM->HBM writeback
        if not acc:
            act_bytes *= nm
            out_bytes *= 2.0 * nj         # y_ref += per j step (r+w)
        hbm = idx_bytes * nb + act_bytes + out_bytes
    elif backend == "msgemm_jnp" and mode == "msgemm":
        kc = _ceil_div(k, d)
        chunk = max(int(consume_chunk), 1)
        nsteps = _ceil_div(kc, chunk)
        steps = nsteps + 1                # scan steps + produce matmul
        produce = 2.0 * costs.produce_table_ops(d) * kc * b
        consume = float(m) * nsteps * chunk * b
        # XLA materializes the LUT in main memory: the spill traffic
        # the fused kernel avoids is real cost here
        hbm = (f32 * m * kc + f32 * k * b + f32 * m * b
               + costs.lut_bytes(k, b, d))
    elif backend in ("int4_pallas", "int4_jnp") or mode == "int4_dequant":
        produce = 2.0 * float(m) * k * b  # dequant + dense matmul
        consume = 0.0
        if backend == "int4_pallas":
            from repro.kernels import ops

            if tm is None or tj is None or tb is None:
                htm, htk, htb = ops.int4_tiles(m, k, b, sb)
                tm, tj, tb = tm or htm, tj or htk, tb or htb
            steps = _ceil_div(m, tm) * _ceil_div(k, tj) * _ceil_div(b, tb)
        else:
            steps = 1
        hbm = (0.5 * m * k + f32 * m * _ceil_div(k, sb)
               + f32 * k * b + f32 * m * b)
    else:                                 # dense bf16 matmul
        produce = 2.0 * float(m) * k * b
        consume = 0.0
        steps = 1
        hbm = 2.0 * m * k + 2.0 * k * b + 2.0 * m * b
    return {
        "launch_s": 1.0,
        "step_s": float(steps),
        "produce_s_per_flop": produce,
        "consume_s_per_op": consume + float(epilogue_ops),
        "hbm_s_per_byte": hbm,
    }


def sample_features(s: Sample) -> dict:
    return features(s.backend, s.mode, s.d, s.scale_block, s.m, s.k, s.b,
                    tm=s.tm, tj=s.tj, tb=s.tb,
                    consume_chunk=s.consume_chunk,
                    acc_in_vmem=s.acc_in_vmem,
                    epilogue_ops=s.epilogue_ops)


def epilogue_op_count(epilogue, m: int, b: int) -> float:
    """Per-invocation elementwise ops of a core.epilogue.Epilogue."""
    if epilogue is None or getattr(epilogue, "is_identity", True):
        return 0.0
    per = _ACT_OPS.get(getattr(epilogue, "act", "none"), 4.0)
    per += 1.0 if getattr(epilogue, "bias", False) else 0.0
    per += 1.0 if getattr(epilogue, "residual", False) else 0.0
    return per * m * b


# =====================================================================
# calibration artifact
# =====================================================================
@dataclass
class Calibration:
    """Fitted per-device model constants + fit diagnostics.  Versioned
    JSON on disk (``calibration.json``); partitioned on (device,
    interpret) so measurements from different execution modes never mix.

    ``constants`` is keyed by backend name: the launch/per-step
    overheads of the Pallas interpreter and an XLA-compiled jnp scan
    differ by orders of magnitude on the same host, so one global
    constant set cannot fit a mixed-backend sample pool.  The ``"*"``
    entry is the pooled fit over every sample and serves backends
    without enough samples for their own fit."""

    device: str
    interpret: bool
    constants: dict[str, dict[str, float]]
    fit: dict = field(default_factory=dict)
    sources: list = field(default_factory=list)
    version: int = CALIBRATION_VERSION
    created_unix: float = 0.0
    # additive (ISSUE 10): fitted COLLECTIVE_CONSTANT_NAMES + fit
    # diagnostics, empty when no shard-variant timings existed
    collective: dict = field(default_factory=dict)

    def matches(self, device: str, interpret: bool) -> bool:
        return self.device == device and self.interpret == bool(interpret)

    def constants_for(self, backend: str | None) -> dict[str, float]:
        return self.constants.get(backend) or self.constants["*"]

    def as_dict(self) -> dict:
        out = {"version": self.version, "device": self.device,
               "interpret": self.interpret,
               "constants": {bk: dict(c)
                             for bk, c in self.constants.items()},
               "fit": dict(self.fit), "sources": list(self.sources),
               "created_unix": self.created_unix}
        if self.collective:
            out["collective"] = dict(self.collective)
        return out

    def save(self, path: str | os.PathLike) -> Path:
        from repro import faults
        from repro.obs import artifacts

        p = Path(path)
        artifacts.atomic_write_json(p, artifacts.stamp_crc(self.as_dict()))
        ev = faults.fire("corrupt_calibration")
        if ev is not None:
            faults.corrupt_file(p, ev)
        return p


def default_calibration_path() -> Path:
    env = os.environ.get("REPRO_CALIBRATION")
    if env:
        return Path(env)
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache")
    return Path(base) / "msgemm-repro" / "calibration.json"


def validate_calibration(doc: dict) -> list[str]:
    """Schema check for a calibration artifact (empty list == valid) —
    same contract as obs.validate_snapshot."""
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["calibration is not an object"]
    if doc.get("version") != CALIBRATION_VERSION:
        errs.append(f"version={doc.get('version')!r} != "
                    f"{CALIBRATION_VERSION}")
    if not isinstance(doc.get("device"), str):
        errs.append("device missing or not a string")
    if not isinstance(doc.get("interpret"), bool):
        errs.append("interpret missing or not a bool")
    consts = doc.get("constants")
    if not isinstance(consts, dict) or not isinstance(
            consts.get("*"), dict):
        errs.append("constants missing or no pooled '*' entry")
    else:
        for bk, block in consts.items():
            if not isinstance(block, dict):
                errs.append(f"constants[{bk!r}] not an object")
                continue
            for name in CONSTANT_NAMES:
                v = block.get(name)
                if not isinstance(v, (int, float)):
                    errs.append(f"constants[{bk!r}].{name} missing or "
                                f"non-numeric")
                elif v < 0 or not math.isfinite(v):
                    errs.append(f"constants[{bk!r}].{name}={v} not "
                                f"finite/>=0")
    fit = doc.get("fit")
    if not isinstance(fit, dict) or "n_samples" not in (fit or {}):
        errs.append("fit block missing n_samples")
    # the collective block is additive and optional — only validated
    # when present.  Its constants may legitimately be negative (they
    # model a *delta* vs the one-shot plan; overlap shows up as a
    # negative hop coefficient), so only finiteness is required.
    coll = doc.get("collective")
    if coll is not None:
        if not isinstance(coll, dict):
            errs.append("collective block not an object")
        else:
            for name in COLLECTIVE_CONSTANT_NAMES:
                v = coll.get(name)
                if not isinstance(v, (int, float)):
                    errs.append(f"collective.{name} missing or "
                                f"non-numeric")
                elif not math.isfinite(v):
                    errs.append(f"collective.{name}={v} not finite")
            if "n_samples" not in coll:
                errs.append("collective block missing n_samples")
    return errs


def validate_calibration_file(path) -> list[str]:
    try:
        doc = json.loads(Path(path).read_text())
    except (OSError, ValueError) as e:
        return [f"unreadable calibration {path}: {e}"]
    return validate_calibration(doc)


def load_calibration(path: str | os.PathLike | None = None, *,
                     device: str | None = None,
                     interpret: bool | None = None,
                     max_age_s: float | None = None) -> Calibration | None:
    """Load a calibration if present, schema-valid, and matching the
    requested (device, interpret) partition — ``None`` otherwise
    (missing, corrupt, wrong version, wrong partition, or older than
    ``max_age_s``: every 'stale' case a consumer must fall back on)."""
    p = Path(path) if path is not None else default_calibration_path()
    from repro.obs import artifacts

    # parse + CRC check; corruption quarantines the file aside
    # (artifact_quarantined_total{artifact="calibration"}) and callers
    # fall back to uncalibrated heuristics, same as a missing file.
    doc = artifacts.load_json_checked(p, "calibration")
    if doc is None:
        return None
    if validate_calibration(doc):
        return None
    cal = Calibration(
        device=doc["device"], interpret=doc["interpret"],
        constants={bk: {k: float(v) for k, v in block.items()}
                   for bk, block in doc["constants"].items()},
        fit=doc.get("fit", {}), sources=doc.get("sources", []),
        version=doc["version"],
        created_unix=float(doc.get("created_unix", 0.0)),
        collective=doc.get("collective") or {})
    if device is None or interpret is None:
        dev, itp = current_partition()
        device = device if device is not None else dev
        interpret = interpret if interpret is not None else itp
    if not cal.matches(device, interpret):
        return None
    if max_age_s is not None and cal.created_unix and \
            time.time() - cal.created_unix > max_age_s:
        return None
    return cal


# =====================================================================
# prediction
# =====================================================================
@dataclass(frozen=True)
class PredictedCost:
    """Predicted wall time of one kernel invocation, by component."""

    t_total_s: float
    t_launch_s: float
    t_step_s: float
    t_produce_s: float
    t_consume_s: float
    t_hbm_s: float
    calibrated: bool
    device: str

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _fallback_constants(device: str) -> dict[str, float]:
    """Uncalibrated constants from the obs.costs hardware table — the
    prediction degrades to a roofline-style bound (no launch/step
    overhead) so predict() always returns *something* ordered."""
    from repro.obs import costs

    import jax

    # partitions are keyed by backend; price this process's own chip
    dev = costs.device(None if device == jax.default_backend() else device)
    return {"launch_s": 0.0, "step_s": 0.0,
            "produce_s_per_flop": 1.0 / dev.matmul_flops,
            "consume_s_per_op": 1.0 / dev.vector_flops,
            "hbm_s_per_byte": 1.0 / dev.mem_bw}


def predict_features(feats: dict, calib: Calibration | None,
                     device: str = "cpu",
                     backend: str | None = None) -> PredictedCost:
    if calib is not None:
        consts = calib.constants_for(backend)
        calibrated, device = True, calib.device
    else:
        consts, calibrated = _fallback_constants(device), False
    terms = {name: consts.get(name, 0.0) * feats.get(name, 0.0)
             for name in CONSTANT_NAMES}
    return PredictedCost(
        t_total_s=sum(terms.values()),
        t_launch_s=terms["launch_s"], t_step_s=terms["step_s"],
        t_produce_s=terms["produce_s_per_flop"],
        t_consume_s=terms["consume_s_per_op"],
        t_hbm_s=terms["hbm_s_per_byte"],
        calibrated=calibrated, device=device)


def predict(plan, spec, m: int, k: int, batch: int, *,
            calib: Calibration | None = None,
            epilogue=None) -> PredictedCost:
    """Predicted wall time for executing (spec, plan) on one
    (batch, k) x (k, m) linear.  ``plan`` is a dispatch ExecPlan (tile
    fields may be None — heuristics fill them exactly like the kernel
    wrappers); ``calib`` None falls back to the roofline-style constant
    table (``calibrated=False`` in the result)."""
    from repro.dispatch.plan import plan_d

    d = plan_d(spec, m, k)
    feats = features(
        plan.backend, spec.mode, max(d, 1), spec.scale_block, m, k, batch,
        tm=plan.tm, tj=plan.tj, tb=plan.tb,
        consume_chunk=plan.consume_chunk, acc_in_vmem=plan.acc_in_vmem,
        epilogue_ops=epilogue_op_count(epilogue, m, batch))
    device = calib.device if calib is not None else current_partition()[0]
    return predict_features(feats, calib, device, backend=plan.backend)


def predict_sample(s: Sample, calib: Calibration | None) -> PredictedCost:
    return predict_features(sample_features(s), calib, s.device,
                            backend=s.backend)


# =====================================================================
# collective-time term (pipelined k-sharded contractions, ISSUE 10)
# =====================================================================
def collective_features(*, impl: str, collective: str, axis_size: int,
                        m: int, b: int, pipeline_chunks: int = 1,
                        dtype_bytes: int = 4) -> dict:
    """(calls, hops, bytes) of resolving one k-sharded linear whose
    per-device partial output is (b, m) f32, under the given collective
    layout.  The hop/byte counts come from the single source of truth
    next to the ring implementations
    (``distributed.collectives.collective_cost``): bytes/hop x hops per
    the issue's model, summed over pipeline chunks."""
    from repro.distributed import collectives as coll

    hops, nbytes = coll.collective_cost(
        impl=impl, collective=collective, axis_size=axis_size,
        elems=m * b, dtype_bytes=dtype_bytes,
        pipeline_chunks=pipeline_chunks)
    return {"calls": max(int(pipeline_chunks), 1), "hops": hops,
            "bytes": nbytes}


def predict_collective(*, calls: float, hops: float, nbytes: float,
                       collective: dict) -> float:
    """Predicted wall-time *delta* (seconds, may be negative) of a
    collective layout relative to the one-shot xla plan of the same
    linear, from a fitted ``Calibration.collective`` block.  Used by
    the autotuner to rank pipelined candidates without measuring all
    chunk counts — only the ordering matters, so the shared one-shot
    baseline cancels."""
    return (collective.get("coll_call_s", 0.0) * (calls - 1)
            + collective.get("coll_hop_s", 0.0) * hops
            + collective.get("coll_byte_s", 0.0) * nbytes)


def collective_rows_from_plan_cache(path: str | os.PathLike | None = None
                                    ) -> list[dict]:
    """Per-variant timing rows from the plan cache's ``shard_variants``
    tables, each annotated with its base key (rows of one key share
    their compute cost, so only deltas within a key are meaningful)."""
    from repro.dispatch import autotune as at

    cache = at.PlanCache(path).load()
    out = []
    for key, var in sorted(cache._shard_variants.items()):
        for row in var.get("rows", []):
            r = dict(row)
            r["key"] = key
            out.append(r)
    return out


def fit_collective(rows: list[dict], *, device: str | None = None,
                   interpret: bool | None = None) -> dict | None:
    """Least-squares fit of COLLECTIVE_CONSTANT_NAMES from shard-variant
    timing rows (one partition).  Each key's one-shot row (pc=1, xla) is
    the per-key baseline; every other row contributes a delta equation
      s - s_base = call_s*(pc-1) + hop_s*(hops-hops_b) + byte_s*(B-B_b)
    Plain (signed) lstsq — negative coefficients are the measured
    overlap benefit.  None when fewer delta rows exist than constants
    (underdetermined fits mislead the ranking; callers fall back to
    measuring every variant)."""
    import numpy as np

    if device is None or interpret is None:
        dev, itp = current_partition()
        device = device if device is not None else dev
        interpret = interpret if interpret is not None else itp
    by_key: dict[str, list[dict]] = {}
    for r in rows:
        if r.get("device") != device or \
                bool(r.get("interpret")) != bool(interpret):
            continue
        by_key.setdefault(r.get("key", "?"), []).append(r)
    A, y = [], []
    for key, group in sorted(by_key.items()):
        base = next((r for r in group
                     if int(r.get("pipeline_chunks", 1)) == 1
                     and r.get("collective_impl") == "xla"), None)
        if base is None:
            continue
        for r in group:
            if r is base:
                continue
            A.append([int(r.get("pipeline_chunks", 1)) - 1,
                      float(r.get("hops", 0)) - float(base.get("hops", 0)),
                      float(r.get("bytes", 0.0))
                      - float(base.get("bytes", 0.0))])
            y.append(float(r["s"]) - float(base["s"]))
    if len(y) < len(COLLECTIVE_CONSTANT_NAMES):
        return None
    A_arr, y_arr = np.asarray(A, float), np.asarray(y, float)
    theta, *_ = np.linalg.lstsq(A_arr, y_arr, rcond=None)
    if not np.isfinite(theta).all():
        return None
    pred = A_arr @ theta
    resid = pred - y_arr
    out = {n: float(v)
           for n, v in zip(COLLECTIVE_CONSTANT_NAMES, theta)}
    out["n_samples"] = len(y)
    out["rms_err_s"] = float(np.sqrt(np.mean(resid ** 2)))
    return out


# =====================================================================
# calibration fit — weighted non-negative least squares
# =====================================================================
def _fit_constants(use: list[Sample]) -> dict[str, float]:
    """NNLS-lite fit of the 5 constants to one sample group.

    Weighted LS: each row is scaled by 1/measured so the objective is
    relative error — a 50us decode candidate counts as much as a 500ms
    prefill row.  Non-negativity by active-set elimination: solve,
    drop the most-negative constant, re-solve (a physical rate can
    never be negative; a dropped constant means the sample set cannot
    resolve it and it contributes 0)."""
    import numpy as np

    t = np.array([s.measured_s for s in use])
    A = np.array([[sample_features(s)[name] for name in CONSTANT_NAMES]
                  for s in use])
    Aw = A / t[:, None]                       # rows scaled by 1/measured
    ones = np.ones(len(use))
    active = list(range(len(CONSTANT_NAMES)))
    theta = np.zeros(len(CONSTANT_NAMES))
    while active:
        sol, *_ = np.linalg.lstsq(Aw[:, active], ones, rcond=None)
        if (sol >= 0).all():
            theta[:] = 0.0
            theta[active] = sol
            break
        active.pop(int(np.argmin(sol)))
    else:
        raise ValueError("calibration fit degenerate: no non-negative "
                         "constants explain the samples")
    return {n: float(v) for n, v in zip(CONSTANT_NAMES, theta)}


MIN_SAMPLES_PER_BACKEND = 3


def fit(samples: list[Sample], *, device: str | None = None,
        interpret: bool | None = None,
        sources: list | None = None) -> Calibration:
    """Fit the model constants from measured samples of one (device,
    interpret) partition.

    Constants are fitted **per backend** (each backend with >=
    ``MIN_SAMPLES_PER_BACKEND`` samples gets its own set) plus a pooled
    ``"*"`` fallback over all samples: interpreter step overhead and
    compiled dispatch overhead differ by orders of magnitude, and a
    single global constant set fitted across both systematically crushes
    whichever backend has fewer samples.  Fit diagnostics are computed
    with the same per-backend dispatch rule :func:`predict_sample` uses.
    """
    import numpy as np

    if device is None or interpret is None:
        dev, itp = current_partition()
        device = device if device is not None else dev
        interpret = interpret if interpret is not None else itp
    use = [s for s in samples
           if s.device == device and s.interpret == bool(interpret)
           and s.measured_s > 0.0]
    if len(use) < MIN_SAMPLES_PER_BACKEND:
        raise ValueError(
            f"calibration needs >= {MIN_SAMPLES_PER_BACKEND} samples in "
            f"partition (device={device!r}, interpret={interpret}); got "
            f"{len(use)} of {len(samples)} total — run the autotuner or "
            f"benchmarks/kernel_microbench.py first")
    constants = {"*": _fit_constants(use)}
    by_backend: dict[str, list[Sample]] = {}
    for s in use:
        by_backend.setdefault(s.backend, []).append(s)
    for bk, group in sorted(by_backend.items()):
        if len(group) >= MIN_SAMPLES_PER_BACKEND:
            try:
                constants[bk] = _fit_constants(group)
            except ValueError:
                pass  # degenerate group: falls back to the pooled fit
    cal = Calibration(device=device, interpret=bool(interpret),
                      constants=constants, sources=list(sources or []),
                      created_unix=time.time())
    rel = np.array([predict_sample(s, cal).t_total_s / s.measured_s - 1.0
                    for s in use])
    worst = int(np.argmax(np.abs(rel)))
    cal.fit = {"n_samples": len(use),
               "n_backends": len(constants) - 1,
               "per_backend_n": {bk: len(g)
                                 for bk, g in sorted(by_backend.items())},
               "rms_rel_err": float(np.sqrt(np.mean(rel ** 2))),
               "max_abs_rel_err": float(np.max(np.abs(rel))),
               "worst_sample": use[worst].desc()}
    return cal


# =====================================================================
# measurement sources
# =====================================================================
def parse_plan_key(key: str) -> dict | None:
    """Invert dispatch.plan.plan_key.  None for unparseable keys."""
    parts = key.split("|")
    if len(parts) < 12:
        return None
    try:
        return {"device": parts[0], "backend": parts[1], "mode": parts[2],
                "d": int(parts[3][1:]), "scale_block": int(parts[4][2:]),
                "storage": parts[5], "codebook": parts[6][2:],
                "m": int(parts[7][1:]), "k": int(parts[8][1:]),
                "b": int(parts[9][1:]), "acc_dtype": parts[10][3:],
                "shard": parts[11][2:]}
    except (ValueError, IndexError):
        return None


def samples_from_plan_cache(path: str | os.PathLike | None = None
                            ) -> tuple[list[Sample], int]:
    """(samples, n_untagged) from the autotuner's persisted per-candidate
    ``timings`` tables.  Rows written before the ``interpret`` tag
    existed cannot be partitioned and are skipped (counted)."""
    from repro.dispatch import autotune as at

    cache = at.PlanCache(path).load()
    out: list[Sample] = []
    untagged = 0
    for key in list(cache._timings):
        info = parse_plan_key(key)
        if info is None:
            continue
        for row in cache.timings(key) or []:
            if "interpret" not in row:
                untagged += 1   # pre-PR7 row: partition unknown, skip
                continue
            out.append(Sample(
                backend=info["backend"], mode=info["mode"], d=info["d"],
                scale_block=info["scale_block"], m=info["m"], k=info["k"],
                b=info["b"], measured_s=float(row["s"]),
                device=info["device"], interpret=bool(row["interpret"]),
                tm=row.get("tm"), tj=row.get("tj"), tb=row.get("tb"),
                consume_chunk=int(row.get("consume_chunk") or 1),
                acc_in_vmem=bool(row.get("acc_in_vmem", True)),
                source=f"plan-cache:{key}"))
    return out, untagged


def samples_from_bench(path: str | os.PathLike) -> list[Sample]:
    """Samples from a schema-2 BENCH_kernels.json: the new-grid and
    legacy-grid timings per shape (heuristic tiles recorded in the
    row).  Epilogue-timing columns are skipped — the unfused baseline
    times jnp ops outside the kernel."""
    doc = json.loads(Path(path).read_text())
    dev = doc.get("device", "cpu")
    interp = bool(doc.get("interpret", dev != "tpu"))
    out: list[Sample] = []
    for r in doc.get("shapes", []):
        tiles = r.get("tiles", {})
        common = dict(
            backend="msgemm_pallas", mode="msgemm", d=int(r["d"]),
            scale_block=int(r["scale_block"]), m=int(r["m"]),
            k=int(r["k"]), b=int(r["b"]), device=dev, interpret=interp,
            tm=tiles.get("tm"), tj=tiles.get("tj"), tb=tiles.get("tb"))
        if r.get("new_kernel_s"):
            out.append(Sample(**common, measured_s=float(r["new_kernel_s"]),
                              acc_in_vmem=True,
                              source=f"bench:{r['shape']}:new"))
        if r.get("legacy_kernel_s"):
            out.append(Sample(**common,
                              measured_s=float(r["legacy_kernel_s"]),
                              acc_in_vmem=False,
                              source=f"bench:{r['shape']}:legacy"))
    return out


def samples_from_snapshot(doc: dict, *, device: str | None = None,
                          interpret: bool | None = None) -> list[Sample]:
    """Samples from ``kernel_gemm_s`` histograms in a metrics snapshot
    (a serve run with tracing on).  Measured = p50 of the series; the
    plan is the shape heuristic (serving resolves heuristic-or-tuned
    plans, so p50 under the heuristic tiles is the honest comparison).
    Histograms whose labels predate the mode/d tags are skipped."""
    if device is None or interpret is None:
        dev, itp = current_partition()
        device = device if device is not None else dev
        interpret = interpret if interpret is not None else itp
    out: list[Sample] = []
    for row in doc.get("histograms", []):
        if row.get("name") != "kernel_gemm_s" or not row.get("count"):
            continue
        lb = row.get("labels", {})
        if not {"backend", "m", "k", "b", "mode", "d", "sb"} <= set(lb):
            continue
        p50 = row.get("p50")
        if not p50:
            continue
        out.append(Sample(
            backend=str(lb["backend"]), mode=str(lb["mode"]),
            d=int(lb["d"]), scale_block=int(lb["sb"]), m=int(lb["m"]),
            k=int(lb["k"]), b=int(lb["b"]), measured_s=float(p50),
            device=device, interpret=bool(interpret),
            source=(f"serve:kernel_gemm_s:{lb['backend']}"
                    f".m{lb['m']}.k{lb['k']}.b{lb['b']}")))
    return out


def samples_from_registry(reg=None) -> list[Sample]:
    """Live-registry variant of :func:`samples_from_snapshot` (the
    ``serve --check-regressions`` path)."""
    from repro import obs

    reg = reg or obs.registry()
    return samples_from_snapshot(reg.snapshot())


# =====================================================================
# regression sentinel
# =====================================================================
def check_regressions(samples: list[Sample], calib: Calibration, *,
                      tolerance: float = DEFAULT_TOLERANCE,
                      min_measured_s: float = 0.0) -> dict:
    """Compare every measured sample against the model.  Returns a
    ranked report (worst ratio first); ``ok`` is False when any sample
    in the calibration's partition exceeds the tolerance band
    (``measured > tolerance * predicted``).  Rows from other partitions
    are listed as skipped, never judged."""
    rows = []
    n_outliers = 0
    skipped = 0
    for s in samples:
        if not s.device == calib.device or \
                s.interpret != calib.interpret:
            skipped += 1
            continue
        pred = predict_sample(s, calib).t_total_s
        floor = max(calib.constants_for(s.backend)["launch_s"], 1e-9)
        ratio = s.measured_s / max(pred, floor)
        outlier = (ratio > tolerance and s.measured_s >= min_measured_s)
        n_outliers += outlier
        rows.append({"desc": s.desc(), "source": s.source,
                     "measured_s": s.measured_s, "predicted_s": pred,
                     "ratio": ratio, "outlier": outlier,
                     "fast": ratio < 1.0 / tolerance})
    rows.sort(key=lambda r: -r["ratio"])
    return {"tolerance": tolerance, "device": calib.device,
            "interpret": calib.interpret, "n_samples": len(rows),
            "n_skipped_other_partition": skipped,
            "n_outliers": n_outliers,
            "n_fast": sum(r["fast"] for r in rows),
            "ok": n_outliers == 0, "rows": rows}


def render_report(report: dict, *, top: int = 20) -> str:
    """Human-readable ranked outlier report (markdown table)."""
    lines = [
        f"# measured-vs-predicted regression report",
        f"partition: device={report['device']} "
        f"interpret={report['interpret']}  "
        f"tolerance: {report['tolerance']:g}x  "
        f"samples: {report['n_samples']} "
        f"(+{report['n_skipped_other_partition']} other-partition)  "
        f"outliers: {report['n_outliers']}  "
        f"verdict: {'OK' if report['ok'] else 'REGRESSION'}",
        "",
        "| rank | ratio | measured | predicted | flag | sample |",
        "|---|---|---|---|---|---|",
    ]
    for i, r in enumerate(report["rows"][:top]):
        flag = ("**OUTLIER**" if r["outlier"]
                else ("fast" if r["fast"] else "ok"))
        lines.append(
            f"| {i + 1} | {r['ratio']:.2f}x | {r['measured_s']:.3e}s | "
            f"{r['predicted_s']:.3e}s | {flag} | {r['desc']} |")
    if len(report["rows"]) > top:
        lines.append(f"| ... | | | | | {len(report['rows']) - top} more |")
    return "\n".join(lines)
