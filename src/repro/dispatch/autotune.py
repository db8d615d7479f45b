"""Shape-keyed autotuner with a persistent JSON plan cache.

For a (spec, m, k, batch, backend, device) key the tuner times every
candidate tile/chunk configuration on synthetic data shaped exactly like
the real call, picks the fastest, and persists the winner — so a serving
process warm-starts from disk and never retunes a shape it (or any
earlier process on the machine) has already measured.

Cache location, first hit wins:

1. ``REPRO_PLAN_CACHE`` env var (file path; CI points it next to the
   benchmark artifacts);
2. ``$XDG_CACHE_HOME/msgemm-repro/plans.json``;
3. ``~/.cache/msgemm-repro/plans.json``.

The JSON is a flat {key: plan-fields} map — human-diffable, and tolerant
on load (a corrupt or newer-versioned file degrades to an empty cache,
never an exception on the serving path).

CLI::

    python -m repro.dispatch.autotune --smoke \
        --cache benchmarks/results/autotune_cache.json

tunes a tiny interpret-mode shape grid twice, asserting the second pass
is served entirely from the reloaded cache (the CI smoke step).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from pathlib import Path

import numpy as np

from repro import obs
from repro.core.spec import QuantSpec
from repro.dispatch import registry
from repro.dispatch.plan import (
    ExecPlan, ExecPolicy, heuristic_plan, plan_d, plan_key,
)

_CACHE_VERSION = 3  # v3: key gains the mesh/shard tag; m/k/b are
# local-shard shapes (a 1-device winner is never replayed as a sharded
# plan, and every mesh shape tunes independently).  v2 files migrate on
# load: their keys gain the unsharded '|sh-' tag — v2 was only ever
# written off-mesh, so the entries keep their value without ever
# leaking into sharded lookups.
# NB: 'interpret' and 'shard' are deliberately not persisted — both are
# runtime/policy overlays (plan() re-attaches the active policy's
# interpret mode and the live mesh's ShardSpec on every cache hit);
# persisting interpret would let an interpret-mode tuning run pin the
# ~100x slower interpreter onto later compiled runs of the same shape.
_PLAN_FIELDS = ("backend", "tm", "tj", "tb", "consume_chunk",
                "acc_in_vmem", "acc_dtype", "epilogue")

# observability hook: incremented per timed candidate (tests assert the
# second run of a cached shape does zero timing)
num_timed_candidates = 0

# how many predicted-best candidates the model-guided search measures
MODEL_TOP_K = 3


def default_cache_path() -> Path:
    env = os.environ.get("REPRO_PLAN_CACHE")
    if env:
        return Path(env)
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache")
    return Path(base) / "msgemm-repro" / "plans.json"


class PlanCache:
    """In-memory view of the persistent plan cache (lazy load)."""

    def __init__(self, path: str | os.PathLike | None = None):
        self.path = Path(path) if path is not None else default_cache_path()
        self._plans: dict[str, ExecPlan] = {}
        self._timings: dict[str, list] = {}
        self._shard_variants: dict[str, dict] = {}
        self._loaded = False

    # ------------------------------------------------------------- io
    def load(self) -> "PlanCache":
        self._loaded = True
        from repro.obs import artifacts

        # parse + CRC check; a corrupt file is quarantined aside
        # (artifact_quarantined_total{artifact="plan_cache"}) and the
        # cache rebuilds empty — warm restarts survive bit rot.
        raw = artifacts.load_json_checked(self.path, "plan_cache")
        if raw is None:
            return self
        try:
            ver = raw.get("version")
            if ver not in (2, _CACHE_VERSION):
                return self
            for key, fields in raw.get("plans", {}).items():
                if ver == 2:
                    # v2 keys never carried a mesh tag (the format
                    # predates sharded planning) and were only written
                    # by unsharded runs: migrate to the '-' tag so they
                    # keep serving single-device lookups but can never
                    # be replayed as sharded plans.
                    key = key + "|sh-"
                self._plans[key] = ExecPlan(
                    **{f: fields.get(f) for f in _PLAN_FIELDS
                       if fields.get(f) is not None},
                    source="autotuned")
            # additive key (still version 3): per-key candidate timing
            # tables from the tuning run that produced each winner.
            # Older readers never look at it; older writers simply drop
            # it on their next save.
            t = raw.get("timings")
            if isinstance(t, dict):
                self._timings.update(t)
            # additive key (still version 3): measured pipelined-
            # collective winners per one-shot base key (ISSUE 10).  v3
            # files written before the table existed simply lack it.
            sv = raw.get("shard_variants")
            if isinstance(sv, dict):
                self._shard_variants.update(sv)
        except (ValueError, TypeError, AttributeError):
            # parsed + CRC-clean but schema-invalid (e.g. hand-edited):
            # quarantine like any other corruption and start empty
            self._plans.clear()
            self._timings.clear()
            self._shard_variants.clear()
            artifacts.quarantine(self.path, "plan_cache", reason="schema")
        return self

    def save(self) -> None:
        from repro import faults
        from repro.obs import artifacts

        payload = {"version": _CACHE_VERSION, "plans": {
            key: {f: getattr(p, f) for f in _PLAN_FIELDS
                  if getattr(p, f) is not None}
            for key, p in sorted(self._plans.items())}}
        if self._timings:
            payload["timings"] = {k: self._timings[k]
                                  for k in sorted(self._timings)}
        if self._shard_variants:
            payload["shard_variants"] = {
                k: self._shard_variants[k]
                for k in sorted(self._shard_variants)}
        artifacts.atomic_write_json(self.path, artifacts.stamp_crc(payload))
        ev = faults.fire("corrupt_plan_cache")
        if ev is not None:
            faults.corrupt_file(self.path, ev)

    # ----------------------------------------------------------- plans
    def get(self, key: str) -> ExecPlan | None:
        if not self._loaded:
            self.load()
        return self._plans.get(key)

    def put(self, key: str, plan: ExecPlan, *, persist: bool = True,
            timings: list | None = None) -> None:
        if not self._loaded:
            self.load()
        self._plans[key] = plan
        if timings is not None:
            self._timings[key] = timings
        if persist:
            self.save()

    def timings(self, key: str) -> list | None:
        """Candidate timing rows recorded when ``key`` was tuned (None
        for keys tuned before timings were persisted)."""
        if not self._loaded:
            self.load()
        return self._timings.get(key)

    # --------------------------------------------- pipelined collectives
    def shard_variant(self, base_key: str) -> dict | None:
        """Measured pipelined-collective winner for the one-shot plan
        keyed by ``base_key``: {'pipeline_chunks', 'collective_impl',
        'rows'} (rows = the per-variant timing table), or None when this
        linear's variants were never tuned."""
        if not self._loaded:
            self.load()
        return self._shard_variants.get(base_key)

    def put_shard_variant(self, base_key: str, variant: dict, *,
                          persist: bool = True) -> None:
        if not self._loaded:
            self.load()
        self._shard_variants[base_key] = variant
        if persist:
            self.save()

    def __len__(self) -> int:
        if not self._loaded:
            self.load()
        return len(self._plans)


_cache: PlanCache | None = None


def cache() -> PlanCache:
    global _cache
    if _cache is None:
        _cache = PlanCache()
    return _cache


def set_cache_path(path: str | os.PathLike | None) -> PlanCache:
    """Point the process at a specific cache file (None -> default)."""
    global _cache
    _cache = PlanCache(path)
    return _cache


# ------------------------------------------------------------ candidates
def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def candidate_plans(spec: QuantSpec, d: int, m: int, k: int, batch: int,
                    backend: str, interpret: bool | None,
                    acc_dtype: str = "float32") -> list[ExecPlan]:
    """Deterministic candidate grid for one shape key.  Always contains
    the heuristic choice, so tuning can only match or beat it.  For the
    Pallas backends the grid also covers the accumulation knob
    (``acc_in_vmem`` False — the legacy per-step formulation), so a shape
    where the reordered grid somehow loses is caught by measurement."""
    from repro.kernels import ops

    pol = ExecPolicy(interpret=interpret, acc_dtype=acc_dtype)
    base = heuristic_plan(spec, d, m, k, batch, backend, pol)
    cands = {base}
    if backend in ("msgemm_pallas", "int4_pallas"):
        cands.add(dataclasses.replace(base, acc_in_vmem=False))
    if backend == "msgemm_jnp":
        for chunk in (1, 2, 4, 8):
            cands.add(dataclasses.replace(base, consume_chunk=chunk))
    elif backend == "msgemm_pallas":
        kc = -(-k // d)
        cpb = spec.scale_block // d
        n = 16 ** d
        tjs = {t for t in (cpb, 2 * cpb, 4 * cpb, 8 * cpb)
               if t <= max(_round_up(kc, cpb), cpb)}
        for tj in tjs:
            for tm in (64, 128, 256):
                for tb in (8, 64, 128):
                    if n * tj * tb * 4 > ops.VMEM_BUDGET:
                        continue
                    tmv = min(tm, _round_up(m, 8))
                    tbv = min(tb, _round_up(batch, 8))
                    cands.add(dataclasses.replace(
                        base, tm=tmv, tj=tj, tb=tbv,
                        # keep the persisted flag truthful: a candidate
                        # whose stripe cannot fit runs (and is timed as)
                        # the legacy accumulation
                        acc_in_vmem=base.acc_in_vmem
                        and ops.acc_stripe_fits(m, tmv, tbv)))
    elif backend == "int4_pallas":
        sb = spec.scale_block
        for tk in (sb, 2 * sb, 4 * sb):
            if tk % 2:
                continue
            for tb in (8, 64, 128):
                cands.add(dataclasses.replace(
                    base, tj=tk, tb=min(tb, _round_up(batch, 8))))
    out = sorted(cands, key=lambda p: (p.tm or 0, p.tj or 0, p.tb or 0,
                                       p.consume_chunk, p.acc_in_vmem))
    # interpret mode multiplies kernel cost ~100x — keep the sweep tiny
    if interpret or (interpret is None and registry.device_kind() != "tpu"):
        out = out[:6]
        if base not in out:
            out.append(base)
        if backend in ("msgemm_pallas", "int4_pallas"):
            legacy = dataclasses.replace(base, acc_in_vmem=False)
            if legacy not in out:  # keep the acc knob measurable
                out.append(legacy)
    return out


# ------------------------------------------------------------ synthetic
def _synthetic_call(spec: QuantSpec, d: int, m: int, k: int, batch: int):
    """Build (params, x) shaped exactly like the real linear call."""
    from repro.core import packing

    rng = np.random.default_rng(0)
    codes = rng.integers(0, 16, size=(m, k)).astype(np.uint8)
    params = {"scales": np.abs(
        rng.standard_normal((m, -(-k // spec.scale_block)))
    ).astype(np.float32) + 0.1}
    if spec.storage == "packed_idx":
        params["idx"] = np.asarray(packing.pack_indices(codes, d))
    else:
        params["u8"] = np.asarray(packing.pack_storage(codes))
    x = rng.standard_normal((batch, k)).astype(np.float32)
    return params, x


def _time_plan(backend: registry.Backend, spec: QuantSpec, p: ExecPlan,
               params, x, k: int, reps: int) -> float:
    global num_timed_candidates
    num_timed_candidates += 1
    import jax

    run = lambda: jax.block_until_ready(
        backend.run(spec, p, params, x, k=k))
    run()  # warmup / compile
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    reg = obs.registry()
    reg.counter("dispatch_autotune_candidates_total",
                help="tile candidates measured",
                backend=backend.name).inc()
    reg.histogram("dispatch_autotune_candidate_s",
                  help="best-of-reps candidate wall time",
                  backend=backend.name).observe(best)
    return best


# ------------------------------------------------------- model pruning
def _model_prune(cands: list[ExecPlan], spec: QuantSpec, d: int, m: int,
                 k: int, batch: int, backend: str, base: ExecPlan,
                 calib) -> list[ExecPlan]:
    """Rank candidates by the calibrated perf model's predicted time and
    keep only the predicted-best ``MODEL_TOP_K``.  The heuristic base
    plan is always in the measured set (replacing the last pick when the
    model ranks it out), so model-guided tuning can only match or beat
    the heuristic — a badly extrapolating calibration costs tuning
    quality, never correctness or a worse-than-default plan."""
    from repro.obs import perfmodel

    def pred(p: ExecPlan) -> float:
        feats = perfmodel.features(
            backend, spec.mode, max(d, 1), spec.scale_block, m, k, batch,
            tm=p.tm, tj=p.tj, tb=p.tb, consume_chunk=p.consume_chunk,
            acc_in_vmem=p.acc_in_vmem)
        return perfmodel.predict_features(feats, calib,
                                          backend=backend).t_total_s

    ranked = sorted(cands, key=pred)
    keep = ranked[:MODEL_TOP_K]
    if base not in keep:
        keep[-1] = base
    return keep


# -------------------------------------------------------------- autotune
def autotune(spec: QuantSpec, m: int, k: int, batch: int, backend: str, *,
             device: str | None = None, interpret: bool | None = None,
             acc_dtype: str = "float32", reps: int = 2,
             persist: bool = True, tag: str = "-",
             search: str = "auto") -> ExecPlan:
    """Measure candidates for one shape key; cache and return the winner.

    ``m/k/batch`` are the shapes the backend will actually execute on
    one device — under a mesh the caller (dispatch.plan / warm) passes
    the *local-shard* shapes and the matching mesh/shard ``tag``, so
    candidates are synthesized and timed at exactly the per-device size
    and the winner is keyed to that mesh shape.

    ``search`` selects the sweep: ``'full'`` measures every candidate;
    ``'model'``/``'auto'`` rank candidates with the calibrated analytic
    perf model (obs.perfmodel) and measure only the predicted-best
    ``MODEL_TOP_K`` (heuristic base always included).  When no
    calibration matching this (device, interpret) partition exists, both
    fall back to the full sweep (``dispatch_autotune_model_fallback_total``
    counts these; ``dispatch_autotune_model_pruned_total`` counts the
    candidates a model-guided run skipped).

    Returns the cached plan immediately when the key is known (from this
    process or a previous one via the JSON file)."""
    device = device or registry.device_kind()
    be = registry.get_backend(backend)
    d = plan_d(spec, m, k)
    key = plan_key(backend, spec, d, m, k, batch, device, acc_dtype, tag)
    hit = cache().get(key)
    if hit is not None:
        # interpret is runtime policy, never part of the cached tuning
        return dataclasses.replace(hit, interpret=interpret)
    pol = ExecPolicy(interpret=interpret, acc_dtype=acc_dtype)
    if not be.tunable:
        return heuristic_plan(spec, d, m, k, batch, backend, pol)
    cands = candidate_plans(spec, d, m, k, batch, backend, interpret,
                            acc_dtype)
    # the partition every timing row in this run belongs to — persisted
    # per row so calibration never mixes interpreter and compiled times
    from repro.obs import perfmodel

    eff_interpret = perfmodel.effective_interpret(interpret)
    pruned = 0
    if search in ("model", "auto") and len(cands) > MODEL_TOP_K:
        calib = perfmodel.load_calibration(device=device,
                                           interpret=eff_interpret)
        reg = obs.registry()
        if calib is None:
            reg.counter("dispatch_autotune_model_fallback_total",
                        help="model-guided searches that fell back to "
                             "the full sweep (no matching calibration)",
                        backend=backend).inc()
        else:
            base = heuristic_plan(spec, d, m, k, batch, backend, pol)
            kept = _model_prune(cands, spec, d, m, k, batch, backend,
                                base, calib)
            pruned = len(cands) - len(kept)
            cands = kept
            reg.counter("dispatch_autotune_model_pruned_total",
                        help="candidates skipped by model-guided search",
                        backend=backend).inc(pruned)
    params, x = _synthetic_call(spec, d, m, k, batch)
    with obs.tracer().span("autotune", key=key, candidates=len(cands),
                           model_pruned=pruned):
        timed = [(_time_plan(be, spec, p, params, x, k, reps), i, p)
                 for i, p in enumerate(cands)]
    best_s, best_i, winner = min(timed)
    winner = dataclasses.replace(winner, source="autotuned")
    # candidate timings ride along in the cache JSON instead of being
    # discarded — they are the calibration data for the analytic perf
    # model (obs.perfmodel) and make regressions diffable across runs.
    # 'interpret'/'device' tag the partition each row was measured under
    # (additive; readers skip untagged pre-tag rows).
    rows = [{"s": t, "tm": p.tm, "tj": p.tj, "tb": p.tb,
             "consume_chunk": p.consume_chunk,
             "acc_in_vmem": p.acc_in_vmem, "winner": i == best_i,
             "interpret": eff_interpret, "device": device}
            for t, i, p in sorted(timed)]
    cache().put(key, winner, persist=persist, timings=rows)
    # same contract as a cache hit: the caller's interpret overlays the
    # winner (a fresh tune and a reload must return identical plans)
    return dataclasses.replace(winner, interpret=interpret)


# ------------------------------------------------- pipelined collectives
# (pipeline_chunks, collective_impl) candidates timed against the
# one-shot plan for every k-sharded linear when ExecPolicy.shard_pipeline
# is 0 (auto).  Chunk counts that don't divide the local k slice (or
# break packed-storage alignment) are dropped per linear.
SHARD_VARIANT_GRID = ((1, "xla"), (1, "ring"), (2, "ring"), (4, "ring"),
                      (2, "xla"))


def _variant_prune(variants, spec, shard, m: int, batch: int,
                   device: str, interpret: bool | None,
                   search: str) -> list:
    """Model-guided pruning of the variant grid: rank by the calibrated
    collective-time term (obs.perfmodel) and keep the one-shot base plus
    the predicted-best few.  No collective calibration -> measure all
    (same fallback contract as the tile sweep)."""
    from repro.distributed import collectives as coll
    from repro.obs import perfmodel

    if search not in ("model", "auto") or len(variants) <= MODEL_TOP_K:
        return list(variants)
    calib = perfmodel.load_calibration(
        device=device, interpret=perfmodel.effective_interpret(interpret))
    reg = obs.registry()
    if calib is None or not getattr(calib, "collective", None):
        reg.counter("dispatch_autotune_model_fallback_total",
                    help="model-guided searches that fell back to "
                         "the full sweep (no matching calibration)",
                    backend="shard_variants").inc()
        return list(variants)
    n = shard.axis_size(shard.k)
    lb = batch // shard.axis_size(shard.batch)
    elems = m * lb  # the partial output one device contracts

    def pred(v):
        pc, impl = v
        hops, nbytes = coll.collective_cost(
            impl=impl, collective=shard.collective, axis_size=n,
            elems=elems, pipeline_chunks=pc)
        return perfmodel.predict_collective(
            calls=pc, hops=hops, nbytes=nbytes, collective=calib.collective)

    ranked = sorted(variants, key=pred)
    keep = ranked[:MODEL_TOP_K]
    if (1, "xla") not in keep:
        keep[-1] = (1, "xla")
    reg.counter("dispatch_autotune_model_pruned_total",
                help="candidates skipped by model-guided search",
                backend="shard_variants").inc(len(variants) - len(keep))
    return keep


def tune_shard_variants(spec: QuantSpec, m: int, k: int, batch: int,
                        backend: str, shard, mesh, *,
                        device: str | None = None,
                        interpret: bool | None = None,
                        acc_dtype: str = "float32", reps: int = 1,
                        persist: bool = True,
                        search: str = "auto") -> dict:
    """Time pipelined-collective variants of one k-sharded linear under
    the live mesh and cache the winner.

    ``m/k/batch`` are GLOBAL shapes and ``shard`` the linear's derived
    one-shot-or-not ShardSpec; each (pipeline_chunks, collective_impl)
    candidate from ``SHARD_VARIANT_GRID`` re-shapes it, gets a kernel
    plan on its per-chunk shapes (cached winner or heuristic — kernel
    tiles and collective layout tune independently), and the whole
    ``run_sharded`` linear (compute + collective, epilogue excluded) is
    timed end-to-end on synthetic global operands.  The winner lands in
    the plan cache's additive ``shard_variants`` table keyed by the
    one-shot base plan key, which is how plan() replays it at trace time
    and how warm restarts skip re-measuring.  Timing rows carry the
    analytic (hops, bytes) of each candidate — the calibration data for
    perfmodel's collective-time term."""
    global num_timed_candidates
    import jax

    from repro.dispatch import shard as _shard
    from repro.distributed import collectives as coll
    from repro.obs import perfmodel

    device = device or registry.device_kind()
    base_shard = dataclasses.replace(shard, pipeline_chunks=1,
                                     collective_impl="xla")
    d = plan_d(spec, m, k)
    blm, blk, blb = base_shard.exec_mkb(m, k, batch)
    base_key = plan_key(backend, spec, d, blm, blk, blb, device,
                        acc_dtype, base_shard.tag())
    hit = cache().shard_variant(base_key)
    if hit is not None:
        return hit

    n = shard.axis_size(shard.k)
    k_local = k // n
    cands, seen = [], set()
    for pc, impl in SHARD_VARIANT_GRID:
        if pc > 1 and (k_local % pc
                       or not _shard._quant_aligned(spec, k_local // pc)):
            continue
        if (pc, impl) not in seen:
            seen.add((pc, impl))
            cands.append((pc, impl))
    cands = _variant_prune(cands, spec, shard, m, batch, device,
                           interpret, search)

    be = registry.get_backend(backend)
    pol = ExecPolicy(interpret=interpret, acc_dtype=acc_dtype)
    params, x = _synthetic_call(spec, d, m, k, batch)
    eff_interpret = perfmodel.effective_interpret(interpret)
    lb = batch // shard.axis_size(shard.batch)
    elems = m * lb
    rows = []
    with obs.tracer().span("autotune.shard_variants", key=base_key,
                           candidates=len(cands)):
        for pc, impl in cands:
            cand = dataclasses.replace(shard, pipeline_chunks=pc,
                                       collective_impl=impl)
            clm, clk, clb = cand.exec_mkb(m, k, batch)
            ckey = plan_key(backend, spec, d, clm, clk, clb, device,
                            acc_dtype, cand.tag())
            p = cache().get(ckey) or heuristic_plan(spec, d, clm, clk, clb,
                                                    backend, pol)
            p = dataclasses.replace(p, interpret=interpret, shard=cand)
            fn = jax.jit(lambda pr, xr, _p=p: _shard.run_sharded(
                be, spec, _p, pr, xr, k=k, mesh=mesh))
            num_timed_candidates += 1
            jax.block_until_ready(fn(params, x))  # compile + warm
            best = float("inf")
            for _ in range(max(reps, 1)):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(params, x))
                best = min(best, time.perf_counter() - t0)
            hops, nbytes = coll.collective_cost(
                impl=impl, collective=cand.collective, axis_size=n,
                elems=elems, pipeline_chunks=pc)
            rows.append({"s": best, "pipeline_chunks": pc,
                         "collective_impl": impl, "hops": hops,
                         "bytes": nbytes, "interpret": eff_interpret,
                         "device": device, "winner": False})
            obs.registry().counter(
                "dispatch_autotune_candidates_total",
                help="tile candidates measured",
                backend="shard_variants").inc()
    best_row = min(rows, key=lambda r: r["s"])
    best_row["winner"] = True
    variant = {"pipeline_chunks": best_row["pipeline_chunks"],
               "collective_impl": best_row["collective_impl"],
               "rows": sorted(rows, key=lambda r: r["s"])}
    cache().put_shard_variant(base_key, variant, persist=persist)
    return variant


def warm(requests, *, policy: ExecPolicy | None = None,
         persist: bool = True) -> dict[str, ExecPlan]:
    """Resolve a batch of collected plan requests up front (engine
    build).  ``requests`` holds ``dispatch.plan.PlanRequest`` entries
    from ``dispatch.collecting()`` (bare (spec, m, k, batch, backend)
    tuples from older callers still work — they warm unsharded).  Shapes
    in the requests are GLOBAL; each request's ShardSpec maps them to
    the local-shard shapes + mesh tag that key the cache, mirroring
    exactly what plan() will compute at trace time.  With
    ``policy.autotune`` each tunable key is measured (and its winner
    persisted); otherwise keys resolve to their cached winner when one
    exists, falling back to the heuristic — heuristic plans are NOT
    written to the cache, so a later autotune run can still improve
    them.

    ``policy.shard_pipeline == 0`` (auto) additionally times pipelined-
    collective variants of every k-sharded request under the live mesh
    (``tune_shard_variants``) before warming its kernel plan — the
    variant winner reshapes the request, so the kernel plan is tuned on
    the winner's per-chunk shapes and plan() finds both at trace time.
    shard_pipeline=0 is its own opt-in: the variant grid is timed even
    when kernel-tile autotuning is off (kernel plans then stay
    heuristic for every variant, so the comparison isolates the
    collective strategy)."""
    policy = policy or ExecPolicy()
    out: dict[str, ExecPlan] = {}
    device = registry.device_kind()
    mesh = None
    if policy.shard_pipeline == 0:
        from repro.distributed.sharding import active_mesh

        mesh = active_mesh()
    for req in dict.fromkeys(requests):
        spec, m, k, batch, backend = req[:5]
        shard = getattr(req, "shard", None)
        tag = getattr(req, "tag", "-")
        d = plan_d(spec, m, k)
        if mesh is not None and shard is not None and shard.k is not None:
            search = (policy.autotune
                      if policy.autotune in ("model", "full") else "auto")
            var = tune_shard_variants(
                spec, m, k, batch, backend, shard, mesh, device=device,
                interpret=policy.interpret, acc_dtype=policy.acc_dtype,
                persist=persist, search=search)
            shard = dataclasses.replace(
                shard, pipeline_chunks=int(var["pipeline_chunks"]),
                collective_impl=str(var["collective_impl"]))
            tag = shard.tag()
        lm, lk, lb = shard.exec_mkb(m, k, batch) if shard is not None \
            else (m, k, batch)
        key = plan_key(backend, spec, d, lm, lk, lb, device,
                       policy.acc_dtype, tag)
        if policy.autotune and registry.get_backend(backend).tunable:
            search = (policy.autotune
                      if policy.autotune in ("model", "full") else "auto")
            p = autotune(spec, lm, lk, lb, backend, device=device,
                         interpret=policy.interpret,
                         acc_dtype=policy.acc_dtype, persist=persist,
                         tag=tag, search=search)
        else:
            hit = cache().get(key)
            p = hit if hit is not None else heuristic_plan(
                spec, d, lm, lk, lb, backend, policy)
        out[key] = dataclasses.replace(p, shard=shard)
    return out


# ------------------------------------------------------------------- CLI
def _smoke(cache_path: str | None) -> int:
    """Tiny interpret-mode tune: write cache -> reload -> assert hits."""
    global num_timed_candidates
    set_cache_path(cache_path)
    shapes = [("msgemm", "msgemm_jnp", 2, 16, 24, 8),
              ("msgemm", "msgemm_pallas", 2, 16, 24, 8),
              ("int4_dequant", "int4_pallas", 2, 16, 32, 8)]
    num_timed_candidates = 0
    plans = {}
    for mode, backend, d, m, k, batch in shapes:
        spec = QuantSpec(mode=mode, d=d, scale_block=4 * d,
                         storage="packed_u8" if backend == "int4_pallas"
                         else "packed_idx")
        p = autotune(spec, m, k, batch, backend, interpret=True, reps=1)
        plans[backend] = p
        print(f"[autotune] {backend:14s} m={m} k={k} b={batch} -> "
              f"tm={p.tm} tj={p.tj} tb={p.tb} chunk={p.consume_chunk} "
              f"({p.source})")
    first_pass = num_timed_candidates
    print(f"[autotune] cache: {cache().path} ({len(cache())} plans, "
          f"{first_pass} candidates timed)")

    # fresh in-memory cache, same file: everything must come from disk
    set_cache_path(cache_path)
    num_timed_candidates = 0
    for mode, backend, d, m, k, batch in shapes:
        spec = QuantSpec(mode=mode, d=d, scale_block=4 * d,
                         storage="packed_u8" if backend == "int4_pallas"
                         else "packed_idx")
        p = autotune(spec, m, k, batch, backend, interpret=True, reps=1)
        assert p == plans[backend], (p, plans[backend])
    assert num_timed_candidates == 0, \
        f"cache reload re-timed {num_timed_candidates} candidates"
    print(f"[autotune] reload: all {len(shapes)} keys served from disk, "
          "0 candidates re-timed")
    return 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny tune + cache write->reload assertion")
    ap.add_argument("--cache", default=None,
                    help="plan-cache JSON path (default: REPRO_PLAN_CACHE "
                         "env or ~/.cache/msgemm-repro/plans.json)")
    ap.add_argument("--mode", default="msgemm",
                    choices=["msgemm", "int4_dequant"])
    ap.add_argument("--backend", default="msgemm_pallas")
    ap.add_argument("--d", type=int, default=3)
    ap.add_argument("--m", type=int, default=256)
    ap.add_argument("--k", type=int, default=256)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args(argv)

    if args.smoke:
        return _smoke(args.cache)
    set_cache_path(args.cache)
    spec = QuantSpec(mode=args.mode, d=args.d, scale_block=12 * args.d)
    p = autotune(spec, args.m, args.k, args.batch, args.backend,
                 interpret=args.interpret or None)
    print(f"[autotune] winner: {p}")
    print(f"[autotune] cache: {cache().path} ({len(cache())} plans)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
