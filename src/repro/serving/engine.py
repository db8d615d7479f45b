"""Continuous-batching serving engine over the paged KV cache.

The engine admits a stream of variable-length requests and interleaves
chunked prefill with batched decode, all through **one shared jitted
step** (runtime.serve.paged_step): a prefill chunk is a (1, C) call and a
decode iteration a (max_slots, 1) call of the same function, so exactly
two executables cover every phase for the lifetime of the engine — no
shape-driven recompiles as requests come and go.

Why this is the msGeMM payoff path: the paper's 4-bit weights free HBM,
and a real server spends that HBM on KV cache.  Paging turns the freed
bytes into *admitted concurrent sequences* (throughput) instead of
padding inside a dense (batch, max_len) cache.

Greedy outputs are token-identical to the static ``runtime.serve.generate``
path for the same prompts (asserted in tests/test_serving.py): chunked
prefill is mathematically exact, and the paged attention view masks
non-owned slots to probability exactly 0.

Resilience (README §Resilience has the full taxonomy): per-request
deadlines with clean cancellation, queue-depth + deadline-aware load
shedding, bounded step retry with exponential backoff (token-identical —
the retried call re-runs from the sequence's paged-KV state), a NaN/Inf
logit guard that quarantines the offending sequence and on repeat
quarantines the suspect dispatch backend and replans down the
degradation ladder, and watchdog hang escalation doing the same.  All
fault *injection* lives behind ``repro.faults`` (zero overhead when
disarmed); the tolerance paths above are always on.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import dispatch, faults, obs
from repro.distributed import sharding as shd
from repro.distributed.watchdog import Watchdog
from repro.models.config import ModelConfig
from repro.runtime import serve as SV
from repro.serving import kv_blocks
from repro.serving.kv_blocks import BlockPool
from repro.serving.request import Phase, Request, Sequence, detokenize
from repro.serving.scheduler import Scheduler

# queue depth / batch occupancy are small integers, not latencies
DEPTH_BUCKETS = (0, 1, 2, 4, 8, 16, 32, 64, 128, 256)


class _StepTimer:
    """Times one engine iteration into serving_step_s{phase=}.  Wall
    time includes device sync only when tracing is on (the engine blocks
    inside the span then); untraced it measures the host dispatch path,
    which is still the right signal for engine-loop overhead."""

    __slots__ = ("engine", "phase", "t0")

    def __init__(self, engine, phase):
        self.engine = engine
        self.phase = phase

    def __enter__(self):
        self.t0 = self.engine._clock()
        return self

    def __exit__(self, *exc):
        obs.registry().histogram(
            "serving_step_s", help="engine iteration wall time",
            phase=self.phase).observe(self.engine._clock() - self.t0)
        return False


class Engine:
    """Continuous-batching engine.

    Parameters
    ----------
    params, cfg : model parameters (optionally quantized) and its config.
    max_slots : decode-batch width (concurrent admitted sequences).
    block_size : KV block size in token positions.
    num_blocks : pool size incl. the reserved scratch block; default sizes
        the pool so paging never preempts (max_slots full-length seqs) —
        pass something smaller to exercise preemption / save HBM.
    max_model_len : per-sequence position budget (prompt + generation).
    prefill_chunk : prefill token budget per engine iteration.
    kv_quant : a ``repro.kvq.KVQuantSpec`` — store the paged pool as
        low-bit codes + scales instead of ``cache_dtype`` values and
        route paged attention through the registered kvq backends
        (in-VMEM dequant on TPU, jnp gather+dequant reference
        elsewhere).  None (default): the unchanged full-precision pool.
    kv_pool_bytes : size the pool by a device-byte budget instead of
        ``num_blocks`` (ignored when ``num_blocks`` is given): the pool
        gets as many blocks as the budget buys at the *actual* storage
        cost (repro.kvq.blocks_for_bytes), so quantized engines admit
        proportionally more resident sequences — and the scheduler,
        which admits against ``BlockPool.capacity``, sees that capacity
        automatically.
    on_token : optional ``f(rid, token, text)`` streaming callback, called
        as each token is generated (text via the synthetic detokenizer).
    backend : force a registered dispatch backend by name for every
        quantized linear (None: per-config/auto selection).
    autotune : measure candidate tile configs for every linear shape this
        engine will step and persist winners to the plan cache.  Plans
        are resolved ONCE here at engine build — an abstract eval_shape
        of both step phases collects the exact (spec, m, k, batch) keys,
        each is tuned/warmed concretely, and the later jit traces only
        ever hit the warm cache.
    autotune_cache : plan-cache JSON path override (None: REPRO_PLAN_CACHE
        env or the default user cache dir).
    mesh : a jax device mesh (e.g. ``launch.mesh.make_mesh((2, 4),
        ("data", "model"))``) — the engine becomes tensor-parallel:
        params and the paged KV pool are laid out per ``mesh_rules``
        (weights TP over 'model', the pool's kvheads over 'model', step
        batches over 'data'), the jitted step traces under the mesh so
        every quantized linear plans local-shard tiles and runs inside a
        shard_map, and ALL exec plans are resolved once at build —
        exactly the autotune warm-up path, whether or not autotuning is
        on — so tracing never derives a shard mid-step.
    mesh_rules : logical-axis rule set (distributed.sharding.RULE_SETS);
        'serve' keeps activations data-parallel and weights TP-resident
        with no FSDP gathers on the hot path.
    shard_collective : 'psum' | 'reduce_scatter' — how row-parallel
        (contraction-sharded) linears resolve partial sums.
    shard_pipeline : contraction-pipelining depth for row-parallel
        linears — 1 (default) keeps the one-shot consume+collective,
        N>1 chunks the local contraction dim so chunk i's ring
        collective overlaps chunk i+1's LUT consume, and 0 lets the
        autotuner time the variant grid per linear and replay the
        winner from the plan cache (``dispatch.autotune
        .tune_shard_variants``).
    shard_impl : 'xla' | 'ring' — collective implementation for the
        contraction reduction; 'ring' uses the explicit ppermute ring
        whose per-hop dataflow the pipelined path can overlap.
    max_queue : admission control — reject (shed) new submissions when
        the waiting queue is already this deep (None: unbounded, the
        historic behavior).  Shed requests come back with status 'shed'
        and count into ``serving_shed_total``.
    deadline_s / ttft_deadline_s : engine-wide default SLOs applied to
        requests that don't carry their own ``Request.deadline_s`` /
        ``ttft_deadline_s`` (None: no deadline).  Expired requests are
        cancelled cleanly with status 'deadline'; a deadline-carrying
        request whose budget is already hopeless against the p95 queue
        wait is shed at submission.
    step_retries / retry_backoff_s : bounded retry of a failed engine
        step with exponential backoff.  The retried call re-runs from
        the sequence's paged-KV state, so recovered output is
        token-identical.  If a failure inside the jitted call consumed
        the donated pool buffer, the engine rebuilds the pool and
        re-prefills everything (also token-exact) instead of retrying.
    watchdog : a ``distributed.watchdog.Watchdog`` (or True for a
        serving-tuned default) that times every step; a hang escalates
        after the step returns — suspect backend quarantined, step
        replanned on the remaining ladder, serving continues.  None
        (default): no per-step timers.
    nan_replan_after : total non-finite-logit events after which the
        guard also quarantines the suspect backend and replans (each
        event always quarantines the offending *sequence*).

    Decode tile presets: plans are resolved per phase shape, so the
    decode batch (max_slots rows of 1 token) plans with its *actual*
    batch — the kernel heuristic sizes tb to round_up(max_slots, 8)
    instead of padding the batch tile to 128, and spends the VMEM freed
    by the narrow stripe on a larger LUT tile (tj) and taller m tiles
    (ops.msgemm_tiles' decode branch) — the produce-amortized sweet spot.
    Under a mesh the same presets apply to the per-device shard shapes.
    """

    def __init__(self, params, cfg: ModelConfig, *, max_slots: int = 4,
                 block_size: int = 16, num_blocks: int | None = None,
                 max_model_len: int | None = None, prefill_chunk: int = 16,
                 cache_dtype=jnp.float32, on_token=None,
                 clock=time.perf_counter, sample_seed: int = 0,
                 backend: str | None = None, autotune: bool | str = False,
                 autotune_cache=None, mesh=None, mesh_rules: str = "serve",
                 shard_collective: str = "psum", shard_pipeline: int = 1,
                 shard_impl: str = "xla", kv_quant=None,
                 kv_pool_bytes: int | None = None,
                 max_queue: int | None = None,
                 deadline_s: float | None = None,
                 ttft_deadline_s: float | None = None,
                 step_retries: int = 2, retry_backoff_s: float = 0.02,
                 watchdog: "Watchdog | bool | None" = None,
                 nan_replan_after: int = 2):
        from repro import kvq

        self.mesh = mesh
        self.mesh_rules = mesh_rules
        self._input_shardings: dict = {}
        if mesh is not None:
            params = jax.device_put(params,
                                    shd.shardings(params, mesh, mesh_rules))
        self.params = params
        if kv_quant is not None:
            cfg = cfg.replace(kv_quant=kv_quant)
        self.cfg = cfg
        self.max_model_len = max_model_len or cfg.max_seq_len
        self.block_size = block_size
        self.max_blocks_per_seq = -(-self.max_model_len // block_size)
        if num_blocks is None:
            if kv_pool_bytes is not None:
                num_blocks = kvq.blocks_for_bytes(
                    cfg, kv_pool_bytes, block_size, cfg.kv_quant,
                    cache_dtype)
            else:
                num_blocks = max_slots * self.max_blocks_per_seq + 1
        self.pool = BlockPool(num_blocks, block_size)
        self._cache_dtype = cache_dtype
        self.kv = SV.init_paged_cache(cfg, num_blocks, block_size,
                                      cache_dtype, mesh=mesh,
                                      rules=mesh_rules)
        self.scheduler = Scheduler(self.pool, max_slots=max_slots,
                                   prefill_chunk=prefill_chunk, clock=clock)
        self.max_slots = max_slots
        self.prefill_chunk = prefill_chunk
        self.on_token = on_token
        self._clock = clock
        self._t0 = clock()
        self._sample_seed = sample_seed
        self._rngs: dict[int, np.random.Generator] = {}
        self.finished: list[Sequence] = []
        self.rejected: list[Sequence] = []  # shed / cancelled / ...
        self.num_prefill_steps = 0
        self.num_decode_steps = 0
        # peak concurrently-admitted sequences observed before the first
        # preemption — the capacity headline BENCH_serve.json reports
        self.max_resident_seqs = 0
        # ---- resilience knobs / state
        self.max_queue = max_queue
        self.default_deadline_s = deadline_s
        self.default_ttft_deadline_s = ttft_deadline_s
        self.step_retries = step_retries
        self.retry_backoff_s = retry_backoff_s
        self.nan_replan_after = nan_replan_after
        self.num_shed = 0
        self.num_step_retries = 0
        self.num_nan_events = 0
        self.num_replans = 0
        self.num_kv_rebuilds = 0
        # any deadline anywhere flips this; the per-step scan is skipped
        # entirely otherwise (zero overhead for deadline-free serving)
        self._deadline_watch = bool(deadline_s or ttft_deadline_s)
        self._hang_flag = threading.Event()
        if watchdog is True:
            # serving steps are ms-scale: mean*hang_factor would be
            # microseconds, so the floor carries the timeout
            watchdog = Watchdog(min_steps=3, min_timeout_s=0.5)
        self._watchdog = watchdog or None
        if self._watchdog is not None and self._watchdog.on_hang is None:
            self._watchdog.on_hang = self._hang_flag.set
        self._export_kv_gauges(num_blocks, cache_dtype)

        def raw_step(params, pool, tokens, positions, wslots, vslots,
                     last_idx):
            logits, pool = SV.paged_step(params, cfg, tokens, pool,
                                         positions, wslots, vslots, last_idx)
            # per-row finite flag, computed on device: the NaN/Inf guard
            # reads B bools per step instead of shipping logits to host
            ok = jnp.all(jnp.isfinite(logits), axis=-1)
            return jnp.argmax(logits, -1).astype(jnp.int32), logits, ok, pool

        # the one shared step: compiled once per phase shape (prefill
        # (1, C), decode (max_slots, 1)); the pool buffer is donated so
        # the KV cache is updated in place across iterations
        self._raw_step = raw_step
        self._step_fn = jax.jit(raw_step, donate_argnums=(1,))

        # execution planning: resolve every linear's ExecPlan once, at
        # build — never per step — so ``exec_plans`` always names what
        # will run.  With no backend/autotune request and no mesh the
        # policy is None and the plans are exactly the per-config
        # default.  Under a mesh the warm-up is how sharded plans + their
        # cache keys come into existence before the trace.
        self._policy = None
        if backend is not None or autotune or mesh is not None:
            if autotune_cache is not None:
                dispatch.set_cache_path(autotune_cache)
            self._policy = dispatch.ExecPolicy(
                backend=backend, autotune=autotune,
                shard_collective=shard_collective,
                shard_pipeline=shard_pipeline, shard_impl=shard_impl)
        self.exec_plans: dict = self._resolve_plans(raw_step)

    def _export_kv_gauges(self, num_blocks: int, cache_dtype) -> None:
        """Pool-capacity gauges (kv_* prefix, NOT serving_*: capacity is
        a property of the built engine, so ``reset_metrics`` between
        measurement streams must not clear it)."""
        from repro import kvq
        from repro.kvq import attention as kvq_attn

        reg = obs.registry()
        spec = self.cfg.kv_quant
        bpt = kvq.bytes_per_token(self.cfg, spec, cache_dtype)
        reg.gauge("kv_pool_bytes",
                  help="device bytes of the paged KV pool").set(
            kvq.pool_bytes(self.cfg, num_blocks, self.block_size, spec,
                           cache_dtype))
        reg.gauge("kv_bytes_per_token",
                  help="pool bytes per token slot across all layers"
                  ).set(bpt)
        reg.gauge("kv_capacity_seqs",
                  help="max-length sequences the pool can hold").set(
            (num_blocks - 1) // self.max_blocks_per_seq)
        if spec is not None:
            W = self.max_blocks_per_seq * self.block_size
            reg.gauge(
                "kv_dequant_hbm_bytes",
                help="HBM bytes of dequantized K/V one layer-step "
                     "materializes (0: in-kernel/VMEM dequant only)",
                backend=kvq_attn.select(spec)).set(
                kvq_attn.dequant_hbm_bytes(spec, self.cfg, self.max_slots,
                                           W))

    def _mesh_ctx(self):
        return (shd.use(self.mesh, self.mesh_rules) if self.mesh is not None
                else contextlib.nullcontext())

    def _resolve_plans(self, raw_step) -> dict:
        """Collect the (spec, m, k, batch, shard) plan keys both step
        phases will request (abstract eval_shape under the mesh —
        nothing is executed), then warm/autotune each concretely so jit
        tracing only hits cache."""
        B, C = self.max_slots, self.prefill_chunk
        W = self.max_blocks_per_seq * self.block_size
        with self._mesh_ctx(), dispatch.using_policy(self._policy), \
                dispatch.collecting() as reqs:
            for nb, nt in ((1, C), (B, 1)):  # prefill chunk, decode batch
                jax.eval_shape(
                    raw_step, self.params, self.kv,
                    np.zeros((nb, nt), np.int32), np.zeros((nb, nt), np.int32),
                    np.zeros((nb, nt), np.int32), np.zeros((nb, W), np.int32),
                    np.zeros((nb,), np.int32))
        with self._mesh_ctx():
            return dispatch.warm(reqs, policy=self._policy)

    def _put_inputs(self, *arrays):
        """Device-place one step's host arrays: leading (row) dim over
        the batch mesh axis when divisible (decode: max_slots over
        'data'), replicated otherwise (prefill's single row).  The
        NamedShardings are memoized per shape — the engine only ever
        steps two shape sets (prefill chunk / decode batch), and the
        rule walk should not rerun once per generated token."""
        if self.mesh is None:
            return arrays
        from jax.sharding import NamedSharding

        out = []
        for a in arrays:
            sharding = self._input_shardings.get(a.shape)
            if sharding is None:
                spec = shd.spec_for(("batch",) + ("none",) * (a.ndim - 1),
                                    a.shape, mesh=self.mesh, kind="act",
                                    rules=self.mesh_rules)
                sharding = NamedSharding(self.mesh, spec)
                self._input_shardings[a.shape] = sharding
            out.append(jax.device_put(a, sharding))
        return tuple(out)

    def _call_step(self, params, pool, *host_arrays):
        """Invoke the shared jitted step with this engine's exec policy
        (and mesh) active — both are consumed at trace time (first call
        per phase shape), where plan() finds the cache pre-warmed by
        ``_resolve_plans``."""
        with self._mesh_ctx(), dispatch.using_policy(self._policy):
            return self._step_fn(params, pool,
                                 *self._put_inputs(*host_arrays))

    def _run_step(self, *host_arrays):
        """The guarded jitted-step call: watchdog timing, fault
        injection, and bounded retry-with-backoff.

        Injected failures (``step_fail``) raise *before* the jitted call
        touches the donated pool, so a retry re-runs from the identical
        paged-KV state and recovered output is token-identical.  An
        organic failure that consumed the donated pool buffer cannot be
        retried in place: the engine rebuilds the pool, preempts every
        running sequence (token-exact re-prefill), and returns None so
        the caller abandons this iteration."""
        attempt = 0
        while True:
            wd = self._watchdog
            try:
                if wd is not None:
                    wd.step_started()
                try:
                    ev = faults.fire("hang")
                    if ev is not None:
                        # a jitted call can't be truly wedged from
                        # Python; stalling past the *armed* hang timer
                        # models it and drives the same escalation
                        floor = 0.0
                        if wd is not None and wd._timer is not None:
                            floor = wd._timer.interval * 1.2
                        time.sleep(max(ev.magnitude, floor))
                    ev = faults.fire("step_fail")
                    if ev is not None:
                        raise faults.InjectedFault("step_fail", ev)
                    return self._call_step(self.params, self.kv,
                                           *host_arrays)
                finally:
                    if wd is not None:
                        wd.step_finished()
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception:
                attempt += 1
                self.num_step_retries += 1
                obs.registry().counter(
                    "serving_step_retries_total",
                    help="engine step failures retried").inc()
                if not self._kv_alive():
                    self._rebuild_kv()
                    return None
                if attempt > self.step_retries:
                    raise
                time.sleep(self.retry_backoff_s * 2 ** (attempt - 1))

    def _kv_alive(self) -> bool:
        for leaf in jax.tree.leaves(self.kv):
            deleted = getattr(leaf, "is_deleted", None)
            if deleted is not None and deleted():
                return False
        return True

    def _rebuild_kv(self) -> None:
        """The jitted step donates the pool buffer; a failure inside the
        call can leave it deleted.  Preempt everything (re-prefill from
        prompt ⊕ generated is token-exact) and re-init the pool so the
        engine keeps serving instead of crashing."""
        self.num_kv_rebuilds += 1
        obs.registry().counter(
            "serving_kv_rebuilds_total",
            help="paged pools re-initialized after a step failure "
                 "consumed the donated buffer").inc()
        for seq in sorted(self.scheduler.running,
                          key=lambda s: -s.admit_seqno):
            self.scheduler.preempt(seq)
        self.kv = SV.init_paged_cache(self.cfg, self.pool.num_blocks,
                                      self.block_size, self._cache_dtype,
                                      mesh=self.mesh, rules=self.mesh_rules)

    # -------------------------------------------------- degradation
    def _escalate_hang(self) -> None:
        """Watchdog hang escalation, run right after the stalled step
        finally returned: count it, quarantine the suspect backend, and
        replan the step on the remaining ladder.  The engine keeps
        serving throughout — nothing here raises."""
        self._hang_flag.clear()
        obs.registry().counter(
            "serving_hang_escalations_total",
            help="watchdog hangs escalated to a backend replan").inc()
        self._replan("hang")

    def _replan(self, reason: str) -> None:
        """Quarantine the backends the current exec plans run on (one
        rung of the pallas -> jnp -> dense-fallback ladder) and re-jit
        the step so the next trace plans on what remains."""
        self.num_replans += 1
        obs.registry().counter(
            "serving_replans_total",
            help="step replans after hang/NaN escalation",
            reason=reason).inc()
        safe = {"dense", "dense_fallback"}
        suspects = sorted({p.backend for p in self.exec_plans.values()}
                          - safe)
        for name in suspects:
            with contextlib.suppress(ValueError):
                dispatch.quarantine_backend(name, reason)
        if self._policy is not None and self._policy.backend in suspects:
            self._policy = dataclasses.replace(self._policy, backend=None)
        # drop the compiled executables; the next call per phase shape
        # re-traces, and plan() now selects on the post-quarantine ladder
        self._step_fn = jax.jit(self._raw_step, donate_argnums=(1,))
        with contextlib.suppress(Exception):
            self.exec_plans = self._resolve_plans(self._raw_step)
        obs.tracer().instant("engine.replan", cat="serving",
                             reason=reason, quarantined=",".join(suspects))

    def _check_finite(self, rows, ok, done: list) -> set:
        """NaN/Inf logit guard.  ``rows``: [(seq, row_index)] consuming
        a token this step; ``ok``: the device-computed per-row finite
        flags.  Non-finite rows (organic or injected) are quarantined —
        the sequence is cancelled cleanly instead of poisoning the
        batch — and once ``nan_replan_after`` events accumulate the
        suspect backend is quarantined too.  Returns the ids of
        quarantined sequences."""
        if not rows:
            return set()
        ok_host = np.asarray(ok)
        bad = {i for (_, i) in rows if not bool(ok_host[i])}
        ev = faults.fire("nan_logits")
        if ev is not None:
            bad.add(rows[int(ev.rng.integers(len(rows)))][1])
        if not bad:
            return set()
        out = set()
        for seq, i in rows:
            if i not in bad:
                continue
            self.num_nan_events += 1
            obs.registry().counter(
                "serving_nan_quarantined_total",
                help="sequences quarantined on non-finite logits").inc()
            done.append(self.cancel(seq, "quarantined"))
            out.add(id(seq))
        if self.num_nan_events >= self.nan_replan_after:
            self._replan("nan_logits")
        return out

    # ------------------------------------------------------------- clock
    @property
    def now(self) -> float:
        return self._clock() - self._t0

    # ------------------------------------------------------------ intake
    def submit(self, req: Request, *, arrival: float | None = None
               ) -> Sequence:
        """Queue a request.  ``arrival`` backdates ``t_arrival`` (engine
        seconds) so latency metrics include queueing delay the engine was
        too busy to observe; default: now.

        Malformed requests (over the model/pool budget) still raise;
        *load* problems do not — a full queue or a hopeless deadline
        sheds the request cleanly instead (returned Sequence has status
        'shed' and never enters the scheduler)."""
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_model_len:
            raise ValueError(
                f"request {req.rid}: prompt+new = {total} exceeds "
                f"max_model_len {self.max_model_len}")
        if self.pool.blocks_for(total) > self.pool.capacity:
            raise ValueError(
                f"request {req.rid}: needs {self.pool.blocks_for(total)} "
                f"blocks, pool holds {self.pool.capacity}")
        if (req.deadline_s is None and req.ttft_deadline_s is None and
                (self.default_deadline_s or self.default_ttft_deadline_s)):
            req = dataclasses.replace(
                req, deadline_s=self.default_deadline_s,
                ttft_deadline_s=self.default_ttft_deadline_s)
        seq = Sequence(req=req,
                       t_arrival=self.now if arrival is None else arrival)
        if req.deadline_s is not None or req.ttft_deadline_s is not None:
            self._deadline_watch = True
        shed_reason = None
        if self.max_queue is not None and \
                len(self.scheduler.waiting) >= self.max_queue:
            shed_reason = "queue_full"
        elif req.deadline_s is not None:
            # deadline-aware admission: if the p95 queue wait already
            # exceeds the whole budget, queueing it is a promise the
            # engine knows it can't keep
            p95 = obs.registry().histogram(
                "serving_queue_wait_s").percentile(95)
            if p95 is not None and p95 > req.deadline_s:
                shed_reason = "deadline_hopeless"
        if shed_reason is not None:
            return self._shed(seq, shed_reason)
        self.scheduler.add(seq)
        obs.registry().counter("serving_requests_submitted_total",
                               help="requests queued").inc()
        obs.tracer().instant("request.submit", cat="serving",
                             rid=req.rid, prompt_tokens=len(req.prompt))
        return seq

    def _shed(self, seq: Sequence, reason: str) -> Sequence:
        seq.status = "shed"
        seq.phase = Phase.FINISHED
        seq.t_finish = self.now
        self.num_shed += 1
        self.rejected.append(seq)
        obs.registry().counter(
            "serving_shed_total",
            help="requests rejected at admission (load shedding)",
            reason=reason).inc()
        obs.tracer().instant("request.shed", cat="serving",
                             rid=seq.req.rid, reason=reason)
        return seq

    def cancel(self, seq: Sequence, reason: str = "cancelled") -> Sequence:
        """Cleanly terminate a queued or running sequence: scheduler
        resources freed, status recorded, counted — never an exception.
        Idempotent on already-terminal sequences."""
        if seq.phase is Phase.FINISHED:
            return seq
        self.scheduler.remove(seq)
        seq.status = reason
        seq.t_finish = self.now
        self.rejected.append(seq)
        obs.registry().counter(
            "serving_cancelled_total",
            help="live sequences cancelled (deadline/disconnect/guard)",
            reason=reason).inc()
        obs.tracer().instant("request.cancel", cat="serving",
                             rid=seq.req.rid, reason=reason,
                             generated=len(seq.generated))
        return seq

    def _enforce_deadlines(self, done: list) -> None:
        now = self.now
        for seq in list(self.scheduler.waiting) + list(self.scheduler.running):
            req = seq.req
            if req.deadline_s is not None and \
                    now - seq.t_arrival > req.deadline_s:
                done.append(self.cancel(seq, "deadline"))
            elif req.ttft_deadline_s is not None and \
                    seq.t_first_token is None and \
                    now - seq.t_arrival > req.ttft_deadline_s:
                done.append(self.cancel(seq, "deadline"))

    # -------------------------------------------------------------- step
    def step(self) -> list[Sequence]:
        """One engine iteration (one prefill chunk OR one decode batch).
        Returns sequences that *terminated* this iteration — finished
        normally (status 'ok') or cancelled (deadline / disconnect /
        quarantine; see ``Sequence.status``)."""
        done: list[Sequence] = []
        injecting = faults.active() is not None
        if injecting:
            ev = faults.fire("latency")
            if ev is not None:
                time.sleep(ev.magnitude)  # step-latency spike
            self._maybe_disconnect(done)
        if self._deadline_watch:
            self._enforce_deadlines(done)
        act = self.scheduler.schedule()
        self._sample_depths()
        if act is None:
            if self.scheduler.waiting and not injecting:
                raise RuntimeError(
                    "engine stalled: waiting requests but nothing running "
                    "and the head cannot be admitted")
            # under injection a transient (injected OOM) admission miss
            # is expected — report idle and let the caller re-step
            return done
        if act[0] == "prefill":
            self._prefill_chunk(act[1], act[2], act[3], done)
        else:
            self._decode_batch(act[1], done)
        if self._hang_flag.is_set():
            self._escalate_hang()
        return done

    def _maybe_disconnect(self, done: list) -> None:
        live = [s for s in self.scheduler.running if not s.done]
        if not live:
            return
        ev = faults.fire("disconnect")
        if ev is not None:
            victim = live[int(ev.rng.integers(len(live)))]
            done.append(self.cancel(victim, "disconnected"))

    def _sample_depths(self) -> None:
        """Per-iteration queue/occupancy samples (gauge = live view for
        /metrics; histogram = distribution for BENCH_serve.json)."""
        reg = obs.registry()
        depth = len(self.scheduler.waiting)
        running = len(self.scheduler.running)
        if self.scheduler.num_preemptions == 0:
            self.max_resident_seqs = max(self.max_resident_seqs, running)
        reg.gauge("serving_queue_depth",
                  help="waiting requests").set(depth)
        reg.gauge("serving_running_seqs",
                  help="admitted sequences").set(running)
        reg.histogram("serving_queue_depth_samples",
                      help="queue depth at each engine iteration",
                      buckets=DEPTH_BUCKETS).observe(depth)
        obs.tracer().counter("queue", waiting=depth, running=running)

    def _prefill_chunk(self, seq: Sequence, start: int, end: int,
                       done: list) -> None:
        C = self.prefill_chunk
        toks = seq.prefill_tokens
        n = end - start
        tokens = np.zeros((1, C), np.int32)
        tokens[0, :n] = toks[start:end]
        positions = (start + np.arange(C, dtype=np.int32))[None]
        ws = kv_blocks.write_slots(seq.blocks, start, n, C,
                                   self.block_size)[None]
        vs = kv_blocks.view_slots(seq.blocks, self.max_blocks_per_seq,
                                  self.block_size)[None]
        last = np.array([n - 1], np.int32)
        with obs.tracer().span("engine.prefill_chunk", cat="serving",
                               rid=seq.req.rid, start=start, end=end), \
                self._step_timer("prefill"):
            out = self._run_step(tokens, positions, ws, vs, last)
            if out is None:  # pool rebuilt; seq was preempted, re-prefills
                return
            tok, logits, ok, self.kv = out
            if obs.tracer().enabled:  # sync so the span covers compute,
                jax.block_until_ready(tok)  # never on the untraced path
        self.num_prefill_steps += 1
        seq.prefill_pos = end
        if end == len(toks):  # prompt fully ingested -> first new token
            if self._check_finite([(seq, 0)], ok, done):
                return
            seq.phase = Phase.DECODE
            self._append(seq, self._pick(seq, tok[0], logits[0]), done)

    def _decode_batch(self, seqs: list[Sequence], done: list) -> None:
        active = []
        for seq in seqs:
            if seq.phase is not Phase.DECODE:
                continue  # evicted as a preemption victim this iteration
            if self.scheduler.grow_for_decode(seq):
                active.append(seq)
        if not active:
            return
        B, bs = self.max_slots, self.block_size
        W = self.max_blocks_per_seq * bs
        tokens = np.zeros((B, 1), np.int32)
        positions = np.zeros((B, 1), np.int32)
        # idle slots write to (distinct offsets of) the scratch block and
        # view only scratch — static shapes, no effect on live sequences
        ws = (np.arange(B, dtype=np.int32) % bs)[:, None]
        vs = np.zeros((B, W), np.int32)
        for seq in active:
            b = seq.slot
            tokens[b, 0] = seq.generated[-1]
            positions[b, 0] = seq.num_tokens - 1
            ws[b] = kv_blocks.write_slots(seq.blocks, seq.num_tokens - 1,
                                          1, 1, bs)
            vs[b] = kv_blocks.view_slots(seq.blocks, self.max_blocks_per_seq,
                                         bs)
        last = np.zeros((B,), np.int32)
        with obs.tracer().span("engine.decode_step", cat="serving",
                               batch=len(active)), \
                self._step_timer("decode"):
            out = self._run_step(tokens, positions, ws, vs, last)
            if out is None:  # pool rebuilt; everyone re-prefills
                return
            tok, logits, ok, self.kv = out
            if obs.tracer().enabled:
                jax.block_until_ready(tok)
        self.num_decode_steps += 1
        obs.registry().histogram(
            "serving_decode_batch_occupancy",
            help="live rows per decode iteration (of max_slots)",
            buckets=DEPTH_BUCKETS).observe(len(active))
        # only live rows are guarded — idle slots attend scratch garbage
        bad = self._check_finite([(s, s.slot) for s in active], ok, done)
        for seq in active:
            if id(seq) in bad:
                continue
            self._append(seq, self._pick(seq, tok[seq.slot],
                                         logits[seq.slot]), done)

    # ---------------------------------------------------------- sampling
    def _pick(self, seq: Sequence, greedy_tok, logits) -> int:
        if seq.req.temperature <= 0.0:
            return int(greedy_tok)
        rng = self._rngs.setdefault(
            seq.req.rid,
            np.random.default_rng(
                np.random.SeedSequence([self._sample_seed, seq.req.rid])))
        scaled = np.asarray(logits, np.float64) / seq.req.temperature
        return int(np.argmax(scaled + rng.gumbel(size=scaled.shape)))

    def _step_timer(self, phase: str):
        return _StepTimer(self, phase)

    def _append(self, seq: Sequence, token: int, done: list) -> None:
        t = self.now
        reg = obs.registry()
        seq.generated.append(token)
        if seq.t_first_token is None:
            seq.t_first_token = t
            reg.histogram("serving_ttft_s",
                          help="time to first token (incl. queueing)"
                          ).observe(t - seq.t_arrival)
        elif seq.t_last_token is not None:
            reg.histogram("serving_intertoken_s",
                          help="gap between consecutive tokens of one "
                               "request").observe(t - seq.t_last_token)
        seq.t_last_token = t
        if self.on_token is not None:
            self.on_token(seq.req.rid, token, detokenize([token]))
        if seq.done:
            seq.t_finish = t
            self.scheduler.finish(seq)
            self.finished.append(seq)
            done.append(seq)
            reg.counter("serving_requests_finished_total",
                        help="requests run to completion").inc()
            reg.histogram("serving_request_latency_s",
                          help="arrival -> last token"
                          ).observe(t - seq.t_arrival)
            obs.tracer().instant("request.finish", cat="serving",
                                 rid=seq.req.rid,
                                 new_tokens=len(seq.generated),
                                 preemptions=seq.preemptions)

    # --------------------------------------------------------------- run
    def run(self, requests, *, wait_for_arrivals: bool = True
            ) -> dict[int, Sequence]:
        """Drive a request stream to completion.  ``arrival_time`` is
        seconds after the call; with ``wait_for_arrivals`` the engine
        sleeps through idle gaps (honest open-loop simulation), otherwise
        future arrivals are pulled forward when it would idle."""
        pending = sorted(requests, key=lambda r: (r.arrival_time, r.rid))
        results: dict[int, Sequence] = {}
        if not self.scheduler.has_work() and not self.finished:
            self._t0 = self._clock()  # fresh engine: run() starts the clock

        def _take():
            req = pending.pop(0)
            # a request queues from its *scheduled* arrival even if the
            # engine was mid-step then (min: pulled-forward arrivals are
            # stamped at actual submission, never in the future)
            seq = self.submit(req, arrival=min(req.arrival_time, self.now))
            if seq.status != "ok":  # shed at admission: terminal already
                results[req.rid] = seq

        while pending or self.scheduler.has_work():
            while pending and pending[0].arrival_time <= self.now:
                _take()
            if not self.scheduler.has_work():
                if not pending:
                    break  # everything left was shed at submission
                if wait_for_arrivals:
                    time.sleep(max(0.0, pending[0].arrival_time - self.now))
                _take()
            for seq in self.step():
                results[seq.req.rid] = seq
        return results

    def reset_metrics(self) -> None:
        """Drop finished-request history, step counters, AND the
        streaming/in-flight aggregates (serving_* registry series: TTFT,
        inter-token, step-time, queue-depth histograms) — e.g. after a
        warmup stream — without touching queued/running work."""
        self.finished = []
        self.rejected = []
        self.num_prefill_steps = 0
        self.num_decode_steps = 0
        self.max_resident_seqs = 0
        self.num_shed = 0
        self.num_step_retries = 0
        self.num_nan_events = 0
        self.num_replans = 0
        self.num_kv_rebuilds = 0
        self.scheduler.num_preemptions = 0
        self.scheduler.num_admitted = 0
        self.scheduler.num_evicted_blocks = 0
        self.scheduler.num_thrash = 0
        obs.registry().reset(prefix="serving_")
        for seq in self.scheduler.running:
            seq.t_last_token = None  # warmup gaps must not leak into the
            # measured stream's first inter-token sample

    # ----------------------------------------------------------- metrics
    def metrics(self) -> dict:
        """Aggregate serving metrics over finished requests.  Every key
        is always present and the call never raises — with 0 finished
        requests (including mid-flight: everything submitted but nothing
        done) counts and rates are 0 / 0.0 and percentiles are ``None``
        ("not measured", distinguishable from a true 0.0 latency); with
        1 finished request the percentiles are that request's value —
        never NaN, never a missing key (callers index
        ``m["tok_per_s"]`` unconditionally; display code should
        coalesce percentiles with ``or 0.0``)."""
        fin = self.finished

        def pct(xs, q):
            if len(xs) == 0:
                return None
            if len(xs) == 1:
                return float(xs[0])
            return float(np.percentile(np.asarray(xs), q))

        gen = sum(len(s.generated) for s in fin)
        span = (max(s.t_finish for s in fin)
                - min(s.t_arrival for s in fin)) if fin else 0.0
        lat = [s.t_finish - s.t_arrival for s in fin]
        ttft = [s.t_first_token - s.t_arrival for s in fin
                if s.t_first_token is not None]
        reg = obs.registry()
        inter = reg.histogram("serving_intertoken_s")
        return {
            "requests": len(fin),
            "generated_tokens": gen,
            "preemptions": self.scheduler.num_preemptions,
            "max_resident_seqs": self.max_resident_seqs,
            "evicted_blocks": self.scheduler.num_evicted_blocks,
            "admitted": self.scheduler.num_admitted,
            "prefill_steps": self.num_prefill_steps,
            "decode_steps": self.num_decode_steps,
            "tok_per_s": gen / span if span > 0 else 0.0,
            "latency_p50_s": pct(lat, 50),
            "latency_p95_s": pct(lat, 95),
            "ttft_p50_s": pct(ttft, 50),
            "ttft_p95_s": pct(ttft, 95),
            # None on an empty reservoir, same contract as pct()
            "intertoken_p50_s": inter.percentile(50),
            "intertoken_p95_s": inter.percentile(95),
            # ---- resilience
            "shed": self.num_shed,
            "cancelled": len(self.rejected) - self.num_shed,
            "step_retries": self.num_step_retries,
            "nan_quarantined": self.num_nan_events,
            "replans": self.num_replans,
            "kv_rebuilds": self.num_kv_rebuilds,
            "preempt_thrash": self.scheduler.num_thrash,
            "queue_wait_p95_s": reg.histogram(
                "serving_queue_wait_s").percentile(95),
        }

    def summary(self) -> dict:
        """Alias of :meth:`metrics` (historic name; keys are a strict
        superset of what it used to return)."""
        return self.metrics()
