"""Measured end-to-end serving throughput (CPU, small model): batched
prefill+decode generation under the three quantized-linear modes, the
weight-bytes each mode ships, and the continuous-batching engine driven
at several simulated arrival rates.  CPU has no MXU/VPU asymmetry, so
this validates the *plumbing* (identical tokens from the two int4 paths)
and quantifies weight compression; the TPU-rate projections live in
phase_rates/roofline.

The continuous-engine rows are also written machine-readable to
``benchmarks/results/BENCH_serve.json`` (tok/s, p50/p95 latency and TTFT
per arrival rate) so the serving perf trajectory is tracked across PRs.

Run standalone with ``--autotune`` to exercise the dispatch autotuner
end-to-end: the engine resolves and persists shape-keyed ExecPlans to
``benchmarks/results/autotune_cache.json`` at build, and a second engine
build asserts every plan is served from the reloaded cache (no
re-timing).
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

import jax

from repro import dispatch, obs
from repro.core.spec import QuantSpec
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.quant import quantize_model
from repro.quant.quantize import quantized_size_bytes
from repro.runtime import serve as SV

RESULTS_JSON = Path(__file__).parent / "results" / "BENCH_serve.json"
AUTOTUNE_CACHE = Path(__file__).parent / "results" / "autotune_cache.json"
SHARD_JSON = Path(__file__).parent / "results" / "BENCH_shard.json"

# BENCH_serve.json / BENCH_shard.json schema history:
#   (unversioned) — PR 2-5: tok/s + latency/TTFT percentiles per run
#   2 — PR 6: adds schema_version; per run preemptions/evicted_blocks/
#       admitted + intertoken percentiles (engine.metrics()), and a
#       "queue_depth" block sampled each scheduler step via the obs
#       registry
#   3 — quantized KV cache (repro.kvq): every continuous run gains
#       kv_bits / kv_bytes_per_token / kv_pool_bytes / max_resident_seqs,
#       the arrival-rate sweep also sweeps kv_bits {16, 8, 4}, and a new
#       "capacity" block measures max resident sequences before first
#       preemption at a FIXED pool-byte budget per kv_bits
#   4 — pipelined collectives: each mesh-sweep mesh now runs a one_shot
#       AND a pipelined (chunked contraction + ring collective) variant
#       (new shard_pipeline / shard_impl columns), every variant carries
#       an "overlap" block computed from the run's shard.compute.* vs
#       shard.collective.* trace spans (fraction of collective time
#       covered by compute), and a "per_device_baselines" block records
#       the single-device engine at EQUAL PER-DEVICE batch (max_slots /
#       data-axis size) — the bar the CI --gate compares mesh throughput
#       against
#   5 — the mesh sweep's "overlap" block is gone: it read host-callback
#       marks staged into the jitted step, which timed the host
BENCH_SERVE_SCHEMA = 5

CFG = ModelConfig(num_layers=4, d_model=256, num_heads=8, num_kv_heads=4,
                  d_ff=1024, vocab_size=8192, max_seq_len=512)


def _bench(params, cfg, batch, new_tokens=16):
    gen = jax.jit(lambda p, b: SV.generate(p, cfg, b,
                                           max_new_tokens=new_tokens,
                                           max_len=64))
    out = gen(params, batch)
    out.block_until_ready()  # compile
    t0 = time.perf_counter()
    out = gen(params, batch)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    return batch["tokens"].shape[0] * new_tokens / dt, out


def run() -> list[str]:
    lines = ["name,us_per_call,derived"]
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, CFG)
    outs = {}
    for mode, d in (("bf16", 3), ("int4_dequant", 3), ("msgemm", 3),
                    ("msgemm", "adaptive")):
        if mode == "bf16":
            p, c = params, CFG
        else:
            qc = QuantSpec(mode=mode, d=d)
            p = quantize_model(params, CFG, qc)
            c = CFG.replace(quant=qc)
        for bsz in (1, 8):
            batch = {"tokens": jax.random.randint(key, (bsz, 16), 0,
                                                  CFG.vocab_size)}
            tps, out = _bench(p, c, batch)
            tag = f"{mode}{'' if d == 3 else '_dadapt'}"
            outs.setdefault(tag, {})[bsz] = out
            lines.append(
                f"serve_throughput/{tag}/b{bsz},{1e6 / tps:.1f},"
                f"tok_per_s={tps:.1f} "
                f"weight_mib={quantized_size_bytes(p) / 2**20:.2f}")
    same = bool((outs["int4_dequant"][8] == outs["msgemm"][8]).mean() > 0.9)
    lines.append(f"serve_throughput/int4_vs_msgemm_tokens_match,0.0,{same}")
    lines += _continuous(params)
    return lines


def _queue_depth() -> dict:
    """Per-step queue-depth distribution for the run just measured.
    ``Engine.reset_metrics()`` clears the ``serving_*`` registry prefix,
    so the histogram holds exactly the measured run's samples."""
    for h in obs.registry().series("histogram"):
        if h.name == "serving_queue_depth_samples":
            return {"samples": h.count,
                    "mean": h.sum / h.count if h.count else 0.0,
                    "max": h.max if h.count else 0.0,
                    "p50": h.percentile(50) or 0.0,
                    "p95": h.percentile(95) or 0.0}
    return {"samples": 0, "mean": 0.0, "max": 0.0, "p50": 0.0, "p95": 0.0}


def _kv_spec(kv_bits: int):
    from repro import kvq

    return None if kv_bits == 16 else kvq.KVQuantSpec(bits=kv_bits)


def _kv_fields(eng, kv_bits: int) -> dict:
    """The schema-3 per-run KV columns."""
    from repro import kvq

    spec = eng.cfg.kv_quant
    return {"kv_bits": kv_bits,
            "kv_bytes_per_token": kvq.bytes_per_token(eng.cfg, spec),
            "kv_pool_bytes": kvq.pool_bytes(eng.cfg, eng.pool.num_blocks,
                                            eng.block_size, spec),
            "max_resident_seqs": eng.max_resident_seqs}


def _capacity(params, n=24, prompt=16, new_tokens=8) -> tuple[dict, list]:
    """Max resident sequences before the first preemption at a FIXED
    pool-byte budget, per kv_bits — the headline capacity claim: the
    budget buys 13 full-precision blocks, and the quantized pools spend
    the same bytes on proportionally more blocks (schema 3).

    Every request is prompt+new = 3 blocks; kv16 fits ~4 resident
    sequences, kv4 fits all 24 — asserted >= 2x kv16."""
    from repro import kvq
    from repro.serving import Engine, poisson_stream

    budget = 13 * 8 * kvq.bytes_per_token(CFG, None)  # 13 f32 blocks
    rows = []
    lines = []
    for kv_bits in (16, 8, 4):
        eng = Engine(params, CFG, max_slots=n, block_size=8,
                     prefill_chunk=16, max_model_len=prompt + new_tokens,
                     kv_quant=_kv_spec(kv_bits), kv_pool_bytes=budget)
        eng.run(poisson_stream(n, CFG.vocab_size,
                               max_new_tokens=new_tokens, rate=0.0,
                               min_prompt=prompt, max_prompt=prompt,
                               seed=5))
        s = eng.metrics()
        row = {"requests": n, "pool_blocks": eng.pool.num_blocks,
               "preemptions": s["preemptions"],
               "tok_per_s": s["tok_per_s"], **_kv_fields(eng, kv_bits)}
        rows.append(row)
        lines.append(
            f"serve_throughput/capacity/kv{kv_bits},0.0,"
            f"max_resident={row['max_resident_seqs']} "
            f"blocks={row['pool_blocks']} "
            f"bytes_per_token={row['kv_bytes_per_token']} "
            f"preemptions={row['preemptions']}")
    by_bits = {r["kv_bits"]: r for r in rows}
    ratio = (by_bits[4]["max_resident_seqs"]
             / max(1, by_bits[16]["max_resident_seqs"]))
    if ratio < 2.0:
        raise SystemExit(
            f"kv4 resident-sequence multiplier {ratio:.2f}x vs kv16 at "
            f"equal pool bytes — expected >= 2x")
    cap = {"pool_byte_budget": budget, "prompt_tokens": prompt,
           "new_tokens": new_tokens, "kv4_resident_multiplier": ratio,
           "runs": rows}
    lines.append(f"serve_throughput/capacity/kv4_multiplier,0.0,"
                 f"{ratio:.2f}x")
    return cap, lines


def _continuous(params, rates=(0.0, 100.0, 25.0), n=10, new_tokens=10
                ) -> list[str]:
    """Continuous-batching engine at several simulated arrival rates
    (rate 0 = closed batch: everything queued at t=0), with the msgemm
    weights additionally swept over kv_bits {16, 8, 4} (schema 3).  A
    warmup stream triggers both jit compiles (prefill + decode shapes)
    per engine before the measured run, so the JSON tracks serving
    throughput, not XLA compile time."""
    from repro.serving import Engine, poisson_stream

    runs = []
    lines = []
    qc = QuantSpec(mode="msgemm", d=3)
    variants = [("bf16", params, CFG, 16)]
    mp, mc = quantize_model(params, CFG, qc), CFG.replace(quant=qc)
    variants += [("msgemm", mp, mc, kv_bits) for kv_bits in (16, 8, 4)]
    for mode, p, c, kv_bits in variants:
        for rate in rates:
            eng = Engine(p, c, max_slots=4, block_size=8, prefill_chunk=16,
                         max_model_len=48, kv_quant=_kv_spec(kv_bits))
            eng.run(poisson_stream(2, c.vocab_size, max_new_tokens=2,
                                   seed=1))  # warmup: compile both shapes
            eng.reset_metrics()
            eng.run(poisson_stream(n, c.vocab_size,
                                   max_new_tokens=new_tokens, rate=rate))
            s = eng.metrics()
            qd = _queue_depth()
            run = {"mode": mode, "arrival_rate": rate, "requests": n,
                   "new_tokens": new_tokens, "queue_depth": qd,
                   **_kv_fields(eng, kv_bits), **s}
            runs.append(run)
            tag = f"continuous/{mode}/kv{kv_bits}/rate{rate:g}"
            lines.append(
                f"serve_throughput/{tag},{1e6 / s['tok_per_s']:.1f},"
                f"tok_per_s={s['tok_per_s']:.1f} "
                f"p50_ms={(s['latency_p50_s'] or 0.0) * 1e3:.1f} "
                f"p95_ms={(s['latency_p95_s'] or 0.0) * 1e3:.1f} "
                f"ttft_p50_ms={(s['ttft_p50_s'] or 0.0) * 1e3:.1f} "
                f"preemptions={s['preemptions']} "
                f"evicted_blocks={s['evicted_blocks']} "
                f"queue_p95={qd['p95']:g}")
    capacity, cap_lines = _capacity(params)
    lines += cap_lines
    RESULTS_JSON.parent.mkdir(parents=True, exist_ok=True)
    RESULTS_JSON.write_text(json.dumps(
        {"bench": "serve_continuous", "schema_version": BENCH_SERVE_SCHEMA,
         "engine": {"max_slots": 4, "block_size": 8, "prefill_chunk": 16},
         "model": {"layers": CFG.num_layers, "d_model": CFG.d_model},
         "runs": runs, "capacity": capacity}, indent=2))
    lines.append(f"serve_throughput/continuous/json,0.0,{RESULTS_JSON}")
    return lines


def run_autotune(cache_path=None) -> list[str]:
    """--autotune: drive the continuous engine with build-time plan
    autotuning, writing the persistent cache, then rebuild and assert the
    cache is reused (zero candidates re-timed)."""
    from repro.dispatch import autotune as at
    from repro.serving import Engine, poisson_stream

    cache_path = Path(cache_path or AUTOTUNE_CACHE)
    cache_path.parent.mkdir(parents=True, exist_ok=True)
    if cache_path.exists():
        cache_path.unlink()  # measure a cold write -> warm reload cycle

    key = jax.random.PRNGKey(0)
    params = T.init_params(key, CFG)
    spec = QuantSpec(mode="msgemm", d=3)
    p, c = quantize_model(params, CFG, spec), CFG.replace(quant=spec)

    def build_and_run():
        eng = Engine(p, c, max_slots=4, block_size=8, prefill_chunk=16,
                     max_model_len=48, autotune=True,
                     autotune_cache=cache_path)
        res = eng.run(poisson_stream(4, c.vocab_size, max_new_tokens=4,
                                     seed=7))
        toks = {rid: seq.generated for rid, seq in res.items()}
        return eng, toks

    at.num_timed_candidates = 0
    eng1, toks1 = build_and_run()
    timed = at.num_timed_candidates
    n_plans = len(eng1.exec_plans)
    assert cache_path.exists() and n_plans, "autotune wrote no plans"

    at.num_timed_candidates = 0
    dispatch.set_cache_path(cache_path)  # fresh in-memory view of the file
    eng2, toks2 = build_and_run()
    assert at.num_timed_candidates == 0, \
        f"warm rebuild re-timed {at.num_timed_candidates} candidates"
    assert toks1 == toks2, "autotuned plans changed generated tokens"

    lines = ["name,us_per_call,derived",
             f"serve_throughput/autotune/cold,0.0,"
             f"plans={n_plans} candidates_timed={timed}",
             f"serve_throughput/autotune/warm,0.0,"
             f"plans={len(eng2.exec_plans)} candidates_timed=0 "
             f"tokens_identical=True",
             f"serve_throughput/autotune/json,0.0,{cache_path}"]
    return lines


def run_mesh_sweep(meshes: list[str], n=8, new_tokens=8,
                   gate=False) -> list[str]:
    """--mesh sweep: drive the continuous engine tensor-parallel over
    each requested mesh ('model=4,data=2' strings) in TWO variants —
    one_shot (the classic consume-then-collective) and pipelined (the
    chunked contraction whose ring collective overlaps the next chunk's
    LUT consume) — assert every variant's greedy tokens are identical to
    the single-device baseline, and write throughput + plan stats to
    BENCH_shard.json (schema 5).

    ``gate`` turns the acceptance claims into a hard exit status:
    pipelined must beat one_shot on the first mesh, and the best mesh
    throughput must be >= the single-device engine at EQUAL PER-DEVICE
    batch."""
    from repro.launch.mesh import mesh_devices
    from repro.launch.serve import parse_mesh
    from repro.serving import Engine, poisson_stream

    key = jax.random.PRNGKey(0)
    params = T.init_params(key, CFG)
    # d=2 / scale_block=8 keeps the packed storage shard-aligned at every
    # k_local this sweep produces, so row-parallel (k-sharded + psum)
    # plans actually form — with d=3 the d-chunk alignment guard rejects
    # them all and the sweep would only ever exercise column-parallel
    spec = QuantSpec(mode="msgemm", d=2, scale_block=8)
    p, c = quantize_model(params, CFG, spec), CFG.replace(quant=spec)
    eng_kw = dict(max_slots=4, block_size=8, prefill_chunk=16,
                  max_model_len=48)
    stream = lambda: poisson_stream(n, c.vocab_size,
                                    max_new_tokens=new_tokens, rate=0.0,
                                    seed=3)

    def drive(mesh, max_slots=None, **extra):
        kw = dict(eng_kw)
        if max_slots is not None:
            kw["max_slots"] = max_slots
        eng = Engine(p, c, **kw, mesh=mesh, **extra)
        eng.run(poisson_stream(2, c.vocab_size, max_new_tokens=2, seed=1))
        eng.reset_metrics()
        res = eng.run(stream())
        toks = {rid: seq.generated for rid, seq in res.items()}
        return (eng, toks,
                {**eng.summary(), "queue_depth": _queue_depth()})

    _, base_toks, base_s = drive(None)
    lines = ["name,us_per_call,derived",
             f"serve_throughput/shard/baseline,"
             f"{1e6 / base_s['tok_per_s']:.1f},"
             f"tok_per_s={base_s['tok_per_s']:.1f}"]

    # equal per-device batch: a mesh with data-axis size D steps D
    # per-device rows for every max_slots global rows, so the fair
    # single-device bar runs max_slots // D slots
    def data_size(mesh):
        return int(dict(mesh.shape).get("data", 1))

    per_dev_base: dict[str, dict] = {}
    for mesh_str in meshes:
        dsz = data_size(parse_mesh(mesh_str))
        slots = max(1, eng_kw["max_slots"] // dsz)
        key_ = str(dsz)
        if key_ in per_dev_base or dsz == 1:
            continue
        _, _, s = drive(None, max_slots=slots)
        per_dev_base[key_] = {"max_slots": slots, **s}
        lines.append(
            f"serve_throughput/shard/baseline_slots{slots},"
            f"{1e6 / s['tok_per_s']:.1f},"
            f"tok_per_s={s['tok_per_s']:.1f} (equal per-device batch "
            f"for data={dsz})")

    # pipelined = shard_pipeline=0: the autotuner times the variant grid
    # per row-parallel linear (cold, into a dedicated cache) and the
    # engine replays the per-linear winners — forcing one global chunk
    # count would mix winners and losers, which is exactly what the
    # variant table exists to avoid
    vcache = SHARD_JSON.parent / "shard_variant_cache.json"
    if vcache.exists():
        vcache.unlink()
    VARIANTS = (("one_shot", dict()),
                ("pipelined", dict(shard_pipeline=0,
                                   autotune_cache=vcache)))
    runs = []
    for mesh_str in meshes:
        mesh = parse_mesh(mesh_str)
        for vname, vkw in VARIANTS:
            eng, toks, s = drive(mesh, **vkw)
            identical = toks == base_toks
            n_sharded = sum(1 for pl in eng.exec_plans.values()
                            if pl.shard is not None)
            n_piped = sum(1 for pl in eng.exec_plans.values()
                          if pl.shard is not None and pl.shard.is_pipelined)
            winners = sorted({f"{pl.shard.pipeline_chunks}."
                              f"{pl.shard.collective_impl}"
                              for pl in eng.exec_plans.values()
                              if pl.shard is not None
                              and pl.shard.k is not None})
            runs.append({"mesh": mesh_str, "devices": mesh_devices(mesh),
                         "variant": vname,
                         "shard_pipeline": vkw.get("shard_pipeline", 1),
                         "shard_impl": vkw.get("shard_impl", "xla"),
                         "variant_winners": winners,
                         "tokens_identical": identical,
                         "plans": len(eng.exec_plans),
                         "sharded_plans": n_sharded,
                         "pipelined_plans": n_piped, **s})
            lines.append(
                f"serve_throughput/shard/{mesh_str}/{vname},"
                f"{1e6 / s['tok_per_s']:.1f},"
                f"tok_per_s={s['tok_per_s']:.1f} sharded_plans={n_sharded} "
                f"pipelined_plans={n_piped} "
                f"tokens_identical={identical}")
            if not identical:
                raise SystemExit(
                    f"sharded engine on mesh {mesh_str} ({vname}) diverged "
                    "from the single-device baseline")
    SHARD_JSON.parent.mkdir(parents=True, exist_ok=True)
    SHARD_JSON.write_text(json.dumps(
        {"bench": "serve_shard", "schema_version": BENCH_SERVE_SCHEMA,
         "engine": eng_kw,
         "model": {"layers": CFG.num_layers, "d_model": CFG.d_model},
         "requests": n, "new_tokens": new_tokens,
         "host_cores": os.cpu_count(),
         "baseline": base_s, "per_device_baselines": per_dev_base,
         "runs": runs}, indent=2))
    lines.append(f"serve_throughput/shard/json,0.0,{SHARD_JSON}")
    if gate:
        lines += _gate_mesh_sweep(meshes[0], runs, per_dev_base)
    return lines


def _gate_mesh_sweep(gate_mesh: str, runs: list, per_dev_base: dict
                     ) -> list[str]:
    """The CI regression gate over a finished sweep (SystemExit -> exit
    1 on any failed claim):

    1. on ``gate_mesh`` the pipelined variant beats one_shot (tok/s);
    2. some mesh run reaches the single-device engine at equal
       per-device batch (the ROADMAP 'mesh serving pays for itself'
       bar).  The bar is scaled by the host's attainable parallel
       fraction min(1, cores / mesh devices): a host that multiplexes V
       fake devices onto C < V cores executes the mesh's per-device
       programs serially, so matching the unscaled single-device number
       is physically impossible there — on real accelerators (C >= V
       workers) the factor is 1 and the bar is the ROADMAP target
       verbatim.
    """
    by = {(r["mesh"], r["variant"]): r for r in runs}
    one, pipe = by[(gate_mesh, "one_shot")], by[(gate_mesh, "pipelined")]
    problems = []
    if pipe["tok_per_s"] <= one["tok_per_s"]:
        problems.append(
            f"pipelined {pipe['tok_per_s']:.2f} tok/s did not beat "
            f"one_shot {one['tok_per_s']:.2f} tok/s on {gate_mesh}")
    cores = os.cpu_count() or 1
    bar = max((b["tok_per_s"] for b in per_dev_base.values()), default=0.0)

    def adjusted_bar(r):
        return bar * min(1.0, cores / max(r["devices"], 1))

    best = max(runs, key=lambda r: r["tok_per_s"] - adjusted_bar(r))
    if per_dev_base and best["tok_per_s"] < adjusted_bar(best):
        problems.append(
            f"best mesh throughput {best['tok_per_s']:.2f} tok/s "
            f"({best['mesh']}/{best['variant']}) below the equal "
            f"per-device-batch single-device bar "
            f"{adjusted_bar(best):.2f} tok/s ({bar:.2f} x "
            f"{min(1.0, cores / max(best['devices'], 1)):.3f} attainable "
            f"on {cores} core(s))")
    if problems:
        raise SystemExit("mesh-sweep gate failed:\n  "
                         + "\n  ".join(problems))
    return [f"serve_throughput/shard/gate,0.0,passed "
            f"pipelined={pipe['tok_per_s']:.2f} "
            f"one_shot={one['tok_per_s']:.2f} "
            f"best={best['tok_per_s']:.2f} "
            f"per_device_bar={adjusted_bar(best):.2f}"]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--autotune", action="store_true",
                    help="exercise build-time plan autotuning + the "
                         "persistent cache write->reload cycle")
    ap.add_argument("--cache", default=None,
                    help=f"plan-cache path (default {AUTOTUNE_CACHE})")
    ap.add_argument("--mesh", action="append", default=None,
                    help="mesh sweep entry, e.g. 'model=4,data=2' "
                         "(repeatable); emits BENCH_shard.json")
    ap.add_argument("--gate", action="store_true",
                    help="with --mesh: exit non-zero unless pipelined "
                         "beats one_shot on the first mesh AND the best "
                         "mesh matches the "
                         "single-device engine at equal per-device batch")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    help="fake N host CPU devices (must be set before "
                         "jax touches the backend)")
    args = ap.parse_args(argv)
    from repro.launch.mesh import force_host_devices

    force_host_devices(args.force_host_devices)
    if args.mesh:
        lines = run_mesh_sweep(args.mesh, gate=args.gate)
    elif args.autotune:
        lines = run_autotune(args.cache)
    else:
        lines = run()
    print("\n".join(lines))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
