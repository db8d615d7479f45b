"""Serving entry point: quantize a model and serve generation with msGeMM
(or int4-dequant / bf16 baseline) weights.

Two engines:

* ``--engine static``      fixed-shape batched prefill+decode
  (runtime.serve.generate) — the original path;
* ``--engine continuous``  the continuous-batching engine with a paged KV
  cache (repro.serving) driven by a simulated Poisson arrival stream of
  mixed-length requests.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma_2b --smoke \
        --quant msgemm --engine continuous --num-requests 6 \
        --backend msgemm_pallas --autotune

Both engines are mesh-aware: ``--mesh model=4,data=2`` serves
tensor-parallel over a device mesh (weights TP over 'model', batches
over 'data', quantized GeMMs inside shard_map with per-shard LUT
produce — see repro.dispatch.shard).  On a CPU host add
``--force-host-devices 8`` to fake the devices:

    PYTHONPATH=src python -m repro.launch.serve --arch gemma_2b --smoke \
        --quant msgemm --engine continuous --mesh model=4,data=2 \
        --force-host-devices 8
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import jax
import numpy as np

from repro import configs, dispatch, obs
from repro.core.spec import QuantSpec
from repro.distributed import sharding as shd
from repro.models import transformer as T
from repro.runtime import serve as SV


def build_model(args):
    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    key = jax.random.PRNGKey(args.seed)
    if args.quant != "bf16":
        spec = QuantSpec(mode=args.quant, d=args.d, scale_block=12 * args.d)
        cfg = cfg.replace(quant=spec)
    # one program; each linear is quantized as its layer group is drawn,
    # so a full-width model never holds its dense weights at once
    params = jax.jit(lambda k: T.init_params(k, cfg))(key)
    if args.quant != "bf16":
        print(f"[serve] quantized weights to {args.quant} (d={args.d})")
    return params, cfg, key


def exec_policy(args) -> dispatch.ExecPolicy | None:
    """The CLI's execution choices as an ExecPolicy (None: defaults)."""
    backend = None if args.backend == "auto" else args.backend
    if backend is None and not args.autotune and args.mesh is None:
        return None
    return dispatch.ExecPolicy(backend=backend, autotune=args.autotune,
                               shard_collective=args.shard_collective,
                               shard_pipeline=args.shard_pipeline,
                               shard_impl=args.shard_impl)


def parse_mesh(s: str):
    """'model=4,data=2' -> a jax mesh with those axes (given order)."""
    from repro.launch import mesh as M

    pairs = [kv.split("=") for kv in s.split(",") if kv]
    axes = tuple(name for name, _ in pairs)
    shape = tuple(int(size) for _, size in pairs)
    need = 1
    for n in shape:
        need *= n
    import jax as _jax

    have = _jax.device_count()
    if need > have:
        raise SystemExit(
            f"--mesh {s} needs {need} devices but only {have} are "
            f"visible; on a CPU host pass --force-host-devices {need} "
            "(or set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} before jax initializes)")
    return M.make_mesh(shape, axes)


def run_static(args, params, cfg, key):
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            key, (args.batch, 16, cfg.d_model))
    elif cfg.frontend == "image_patches":
        batch["patch_embeds"] = jax.random.normal(
            key, (args.batch, cfg.num_patches, cfg.d_model))

    policy = exec_policy(args)
    if policy is not None and policy.autotune:
        # plans must be tuned OUTSIDE the trace: collect the shape keys
        # abstractly, warm them concretely, then generate for real
        with dispatch.collecting() as reqs:
            jax.eval_shape(lambda p, b: SV.generate(
                p, cfg, b, max_new_tokens=args.new_tokens), params, batch)
        plans = dispatch.warm(reqs, policy=policy)
        print(f"[serve] resolved {len(plans)} exec plans before trace "
              f"(cache={dispatch.cache().path})")

    t0 = time.time()
    out = SV.generate(params, cfg, batch, max_new_tokens=args.new_tokens)
    out.block_until_ready()
    dt = time.time() - t0
    tput = args.batch * args.new_tokens / dt
    print(f"[serve] generated {out.shape} in {dt:.2f}s "
          f"({tput:.1f} tok/s incl. compile)")
    print(out[:, :12])
    return out


def make_request_stream(args, cfg):
    """Mixed-length prompts with Poisson (exponential inter-arrival)
    timing — deterministic in --seed."""
    from repro.serving import poisson_stream

    return poisson_stream(args.num_requests, cfg.vocab_size,
                          max_new_tokens=args.new_tokens,
                          rate=args.arrival_rate,
                          min_prompt=max(1, args.prompt_len // 4),
                          max_prompt=args.prompt_len, seed=args.seed)


def kv_spec_from_args(args, params, cfg):
    """--kv-bits/--kv-codebook -> KVQuantSpec (None at 16 bits).  A
    learned codebook is fitted here, once, from the model's own K/V
    activations on a synthetic batch (repro.kvq.fit)."""
    if args.kv_bits == 16:
        if args.kv_codebook == "learned":
            print("[serve] --kv-codebook learned ignored at --kv-bits 16")
        return None
    codebook = None
    if args.kv_codebook == "learned":
        if args.kv_bits != 4:
            print("[serve] --kv-codebook learned ignored at --kv-bits 8 "
                  "(codebooks are a 4-bit construct)")
        else:
            from repro import kvq

            codebook = kvq.fit_kv_codebook(params, cfg, seed=args.seed)
            print("[serve] fitted 16-entry KV codebook from model "
                  "activations")
    from repro.kvq import KVQuantSpec

    return KVQuantSpec(bits=args.kv_bits, codebook=codebook)


def run_continuous(args, params, cfg, mesh=None):
    from repro.serving import Engine

    kv_spec = kv_spec_from_args(args, params, cfg)
    if kv_spec is not None:
        print(f"[serve] quantized KV cache: {kv_spec.describe()}")
    max_len = args.prompt_len + args.new_tokens
    engine = Engine(params, cfg,
                    max_slots=args.max_slots,
                    block_size=args.block_size,
                    num_blocks=args.num_blocks or None,
                    max_model_len=max_len,
                    prefill_chunk=args.prefill_chunk,
                    backend=None if args.backend == "auto" else args.backend,
                    autotune=args.autotune,
                    autotune_cache=args.autotune_cache,
                    mesh=mesh, mesh_rules=args.mesh_rules,
                    shard_collective=args.shard_collective,
                    shard_pipeline=args.shard_pipeline,
                    shard_impl=args.shard_impl,
                    kv_quant=kv_spec,
                    kv_pool_bytes=(int(args.kv_pool_mib * 2**20)
                                   if args.kv_pool_mib else None),
                    max_queue=args.max_queue or None,
                    deadline_s=args.deadline_s or None,
                    ttft_deadline_s=args.ttft_deadline_s or None,
                    watchdog=args.watchdog or None)
    if mesh is not None:
        n_sharded = sum(1 for p in engine.exec_plans.values()
                        if p.shard is not None)
        print(f"[serve] mesh {dict(mesh.shape)}: {len(engine.exec_plans)} "
              f"plans resolved at build, {n_sharded} sharded "
              f"(rules={args.mesh_rules}, "
              f"collective={args.shard_collective})")
    reqs = make_request_stream(args, cfg)
    print(f"[serve] continuous engine: {len(reqs)} requests, prompt lens "
          f"{sorted(len(r.prompt) for r in reqs)}, rate="
          f"{args.arrival_rate or 'inf'} req/s, block_size="
          f"{args.block_size}, slots={args.max_slots}")
    if engine.exec_plans:
        print(f"[serve] resolved {len(engine.exec_plans)} exec plans at "
              f"build (autotune={'on' if args.autotune else 'off'}, "
              f"cache={dispatch.cache().path})")
    t0 = time.time()
    results = engine.run(reqs)
    dt = time.time() - t0
    for rid in sorted(results):
        seq = results[rid]
        m = seq.metrics()
        if m["status"] != "ok":
            print(f"  req {rid}: prompt={m['prompt_tokens']:3d} "
                  f"new={m['new_tokens']:3d} status={m['status']}")
            continue
        print(f"  req {rid}: prompt={m['prompt_tokens']:3d} "
              f"new={m['new_tokens']:3d} ttft={m['ttft_s'] * 1e3:7.1f}ms "
              f"lat={m['latency_s'] * 1e3:7.1f}ms "
              f"preempt={m['preemptions']} tok={seq.generated[:8]}")
    s = engine.summary()
    # percentiles are None when nothing finished — coalesce for display
    print(f"[serve] {s['generated_tokens']} tokens in {dt:.2f}s "
          f"({s['tok_per_s']:.1f} tok/s) "
          f"p50={(s['latency_p50_s'] or 0.0) * 1e3:.1f}ms "
          f"p95={(s['latency_p95_s'] or 0.0) * 1e3:.1f}ms "
          f"preemptions={s['preemptions']}")
    if s["shed"] or s["cancelled"] or s["step_retries"] or s["replans"]:
        print(f"[serve] resilience: shed={s['shed']} "
              f"cancelled={s['cancelled']} retries={s['step_retries']} "
              f"nan_quarantined={s['nan_quarantined']} "
              f"replans={s['replans']}")

    if args.check:
        live = {rid: seq for rid, seq in results.items()
                if seq.status == "ok"}
        bad = 0
        for rid, seq in live.items():
            toks = np.array([list(seq.req.prompt)], np.int32)
            ref = SV.generate(params, cfg, {"tokens": toks},
                              max_new_tokens=seq.req.max_new_tokens)
            if [int(t) for t in np.asarray(ref)[0]] != seq.generated:
                bad += 1
        print(f"[serve] static-path parity check: "
              f"{len(live) - bad}/{len(live)} identical "
              f"({len(results) - len(live)} non-ok skipped)")
        if bad:
            raise SystemExit("continuous engine diverged from static path")
    return results


def serve(args, mesh):
    """Build the model and run the engine the CLI asked for."""
    params, cfg, key = build_model(args)
    if args.engine == "continuous":
        return run_continuous(args, params, cfg, mesh)
    if args.kv_bits != 16 or args.kv_pool_mib:
        print("[serve] --kv-bits/--kv-pool-mib apply to the paged "
              "pool only; ignored by --engine static", file=sys.stderr)
    if args.autotune_cache is not None:
        dispatch.set_cache_path(args.autotune_cache)
    if mesh is None:
        with dispatch.using_policy(exec_policy(args)):
            return run_static(args, params, cfg, key)
    params = jax.device_put(params,
                            shd.shardings(params, mesh, args.mesh_rules))
    with shd.use(mesh, args.mesh_rules), \
            dispatch.using_policy(exec_policy(args)):
        return run_static(args, params, cfg, key)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default="msgemm",
                    choices=["bf16", "int4_dequant", "msgemm"])
    ap.add_argument("--d", type=int, default=3, help="LUT depth (paper d)")
    ap.add_argument("--engine", default="static",
                    choices=["static", "continuous"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    # continuous-engine knobs
    ap.add_argument("--num-requests", type=int, default=6)
    ap.add_argument("--arrival-rate", type=float, default=50.0,
                    help="mean req/s of the Poisson stream (<=0: all at t=0)")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="KV pool blocks (0: sized to never preempt)")
    ap.add_argument("--prefill-chunk", type=int, default=8)
    # quantized KV cache (repro.kvq; continuous engine only)
    ap.add_argument("--kv-bits", type=int, default=16, choices=[16, 8, 4],
                    help="paged KV pool storage: 16 = full precision, "
                         "8/4 = quantized codes + per-slot scales")
    ap.add_argument("--kv-codebook", default="uniform",
                    choices=["uniform", "learned"],
                    help="4-bit code map: uniform int4 grid or a 16-entry "
                         "codebook fitted from the model's K/V activations")
    ap.add_argument("--kv-pool-mib", type=float, default=0,
                    help="size the KV pool by a device-byte budget (MiB) "
                         "instead of --num-blocks; quantized pools fit "
                         "proportionally more blocks")
    ap.add_argument("--check", action="store_true",
                    help="assert token parity vs the static generate path")
    # resilience (continuous engine; README §Resilience)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="shed submissions beyond this waiting-queue "
                         "depth (0: unbounded)")
    ap.add_argument("--deadline-s", type=float, default=0,
                    help="default per-request total-latency SLO; expired "
                         "requests are cancelled cleanly (0: none)")
    ap.add_argument("--ttft-deadline-s", type=float, default=0,
                    help="default first-token SLO (0: none)")
    ap.add_argument("--watchdog", action="store_true",
                    help="arm the per-step hang watchdog (hangs escalate "
                         "to a backend quarantine + replan)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="arm deterministic fault injection: 'all' or "
                         "'cls:p=..,after=..,max=..,mag=..;cls2' "
                         "(classes: repro.faults.CLASSES; overrides "
                         "REPRO_FAULTS)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the injected-fault schedule")
    # execution planning (repro.dispatch)
    ap.add_argument("--backend", default="auto",
                    choices=["auto"] + dispatch.backend_names(),
                    help="force a registered execution backend "
                         "(auto: capability+priority selection)")
    ap.add_argument("--autotune", nargs="?", const=True, default=False,
                    choices=["model", "full"], metavar="MODE",
                    help="time candidate tile configs per linear shape and "
                         "persist winners to the plan cache; bare flag "
                         "auto-selects model-guided search when a perf-model "
                         "calibration exists, '=model'/'=full' force the "
                         "pruned/exhaustive sweep")
    ap.add_argument("--autotune-cache", default=None,
                    help="plan-cache JSON path (default: REPRO_PLAN_CACHE "
                         "env or ~/.cache/msgemm-repro/plans.json)")
    # sharded serving (repro.dispatch.shard over a device mesh)
    ap.add_argument("--mesh", default=None,
                    help="serve tensor-parallel over a device mesh, e.g. "
                         "'model=4,data=2' (axis order preserved)")
    ap.add_argument("--mesh-rules", default="serve",
                    choices=sorted(shd.RULE_SETS),
                    help="logical-axis rule set for params/activations")
    ap.add_argument("--shard-collective", default="psum",
                    choices=["psum", "reduce_scatter"],
                    help="contraction collective for row-parallel linears")
    ap.add_argument("--shard-pipeline", type=int, default=1,
                    metavar="CHUNKS",
                    help="pipeline the TP contraction: split the local "
                         "contraction dim into CHUNKS slices so chunk i's "
                         "collective overlaps chunk i+1's LUT consume "
                         "(1: one-shot; 0: autotune the variant grid and "
                         "replay the cached winner)")
    ap.add_argument("--shard-impl", default="xla",
                    choices=sorted(dispatch.shard.COLLECTIVE_IMPLS),
                    help="contraction-collective implementation: 'xla' "
                         "native psum/psum_scatter, 'ring' explicit "
                         "ppermute ring (overlappable per hop)")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    help="fake N host CPU devices (sets XLA_FLAGS; must "
                         "run before jax touches the backend)")
    # observability (repro.obs) — all off by default, near-zero cost off
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write a versioned registry snapshot "
                         "(obs.metrics) on exit")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="write a jax.profiler trace of the run under DIR "
                         "(device ops with their model scopes and the "
                         "engine spans on one clock; a Perfetto file "
                         "beside the .xplane.pb)")
    ap.add_argument("--prom-port", type=int, default=0,
                    help="expose /metrics in Prometheus text format on "
                         "this port for the lifetime of the run")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache
    from repro.launch.mesh import force_host_devices

    use_compile_cache()

    force_host_devices(args.force_host_devices)
    mesh = parse_mesh(args.mesh) if args.mesh else None

    from repro import faults

    if args.faults:
        plan = faults.FaultPlan(faults.parse_spec(args.faults),
                                seed=args.fault_seed)
        faults.arm(plan)
        print(f"[serve] fault injection armed: {plan.describe()}")
    else:
        plan = faults.plan_from_env()  # REPRO_FAULTS / REPRO_FAULT_SEED
        if plan is not None:
            faults.arm(plan)
            print(f"[serve] fault injection armed from env: "
                  f"{plan.describe()}")

    prom = None
    if args.prom_port:
        prom = obs.serve_prometheus(args.prom_port)
        print(f"[serve] prometheus /metrics on port "
              f"{prom.server_address[1]}")

    profile = (jax.profiler.trace(args.trace_out,
                                  create_perfetto_trace=True)
               if args.trace_out else contextlib.nullcontext())
    try:
        with profile:
            return serve(args, mesh)
    finally:
        if args.trace_out:
            print(f"[serve] wrote profiler trace under {args.trace_out}")
        if args.metrics_json:
            snap = obs.registry().snapshot(extra={
                "arch": args.arch, "quant": args.quant,
                "engine": args.engine, "mesh": args.mesh,
                "backend": args.backend, "kv_bits": args.kv_bits,
                "kv_codebook": args.kv_codebook})
            with open(args.metrics_json, "w") as f:
                json.dump(snap, f, indent=1)
            print(f"[serve] wrote metrics snapshot {args.metrics_json}")
        if prom is not None:
            prom.shutdown()


if __name__ == "__main__":
    main()
