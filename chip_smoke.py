"""Smoke run of the serving path on TPU, at gemma-2b's published widths.

    python chip_smoke.py                # one chip: the main serving path
    python chip_smoke.py --four-chips   # four chips: tensor-parallel serving

The one-chip phase builds gemma-2b (configs/gemma_2b.py ``CONFIG``: 18
layers, d_model 2048, d_ff 16384, vocab 256000) with random msGeMM
weights at the serve CLI's default d=3, made from ``--seed``.  It serves
8 seeded requests (prompts of 64-256 tokens, 32 new tokens each) through
the continuous-batching engine with heuristic plans, and checks:

* every quantized linear's plan is the backend the registry picks on a
  TPU (the fused ``msgemm_pallas`` kernel), running compiled;
* no step retry, replan, quarantined backend or NaN event, and every
  request finished ``ok``;
* greedy tokens equal the static ``generate`` path's (``serve --check``);
* one prefill's logits through the kernel match a dequantize-then-matmul
  reference, both in float32 at ``precision=HIGHEST``, within
  ``LOGIT_TOL``.

``--four-chips`` runs only the tensor-parallel engine (``model=4``) and a
one-chip engine on ``devices[0]`` over the same requests, and checks
that their tokens are identical and that the mesh plans are sharded.

Every line before the last is a log line; the wall times there are smoke
figures, not metrics.  The last line is one JSON object naming the
device.  Where JAX's first device is not a TPU, or the ``repro`` package
is not beside this file, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
LOGIT_TOL = 1e-3  # max |kernel - reference| over max |reference|, f32
D = 3             # serve CLI default LUT depth
BLOCK_SIZE = 16
PREFILL_CHUNK = 64


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        log(f"FAIL: {what}")
        raise SystemExit(1)
    log(f"ok: {what}")


def make_requests(vocab: int, lens, new_tokens: int, seed: int):
    import numpy as np

    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new_tokens=new_tokens,
                    prompt=tuple(int(t) for t in
                                 rng.integers(0, vocab, size=n)))
            for i, n in enumerate(lens)]


def serve(params, cfg, reqs, *, slots: int, mesh=None):
    """Run ``reqs`` through a fresh engine; (engine, {rid: tokens}, s)."""
    from repro.serving import Engine

    max_len = max(len(r.prompt) + r.max_new_tokens for r in reqs)
    eng = Engine(params, cfg, max_slots=slots, block_size=BLOCK_SIZE,
                 max_model_len=max_len, prefill_chunk=PREFILL_CHUNK,
                 mesh=mesh)
    t0 = time.perf_counter()
    results = eng.run(reqs, wait_for_arrivals=False)
    dt = time.perf_counter() - t0
    check(sorted(results) == [r.rid for r in reqs]
          and all(s.status == "ok" for s in results.values()),
          f"all {len(reqs)} requests finished ok")
    return eng, {rid: list(s.generated) for rid, s in results.items()}, dt


def check_engine(eng, cfg) -> dict:
    """Plans on the registry's TPU choice, compiled; no resilience event.
    Returns the backend counts."""
    from repro import dispatch
    from repro.kernels.mode import resolve_interpret

    want = dispatch.select_backend(cfg.quant, D, "tpu").name
    plans = list(eng.exec_plans.values())
    counts: dict = {}
    for p in plans:
        counts[p.backend] = counts.get(p.backend, 0) + 1
    log(f"backend counts: {counts}")
    check(bool(plans) and all(p.backend == want for p in plans),
          f"every exec plan runs {want}, the registry's TPU choice")
    check(all(resolve_interpret(p.interpret) is False for p in plans),
          "every exec plan runs compiled (interpret false)")
    check(all(p.source == "heuristic" for p in plans),
          "every plan came from the heuristic (no plan-cache file)")
    m = eng.metrics()
    check(m["step_retries"] == 0 and m["replans"] == 0
          and m["nan_quarantined"] == 0 and m["kv_rebuilds"] == 0
          and not dispatch.quarantined(),
          "zero step retries, replans, quarantined backends and NaN events")
    return counts


def static_parity(params, cfg, reqs, tokens) -> None:
    """Greedy engine tokens == the static generate path's, per request."""
    import numpy as np

    from repro.runtime import serve as SV

    bad = 0
    for r in reqs:
        ref = SV.generate(params, cfg, {"tokens": np.array([r.prompt],
                                                           np.int32)},
                          max_new_tokens=r.max_new_tokens)
        bad += [int(t) for t in np.asarray(ref)[0]] != tokens[r.rid]
    check(bad == 0, f"static-path parity: {len(reqs) - bad}/{len(reqs)} "
                    f"requests token-identical")


def logit_check(params, cfg, prompt) -> None:
    """One prefill through the fused kernel vs dequantize-then-matmul
    (the ``dense_fallback`` backend), f32 activations, HIGHEST precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import dispatch
    from repro.models import transformer as T

    cfg32 = cfg.replace(dtype="float32")
    toks = jnp.asarray([prompt], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: T.forward(p, cfg32, {"tokens": t})[0])(
            params, toks)
        with dispatch.using_policy(
                dispatch.ExecPolicy(backend="dense_fallback")):
            want = jax.jit(
                lambda p, t: T.forward(p, cfg32, {"tokens": t})[0])(
                    params, toks)
    got, want = np.asarray(got), np.asarray(want)
    check(bool(np.isfinite(got).all()) and got.shape == want.shape
          == (1, len(prompt), cfg.vocab_size),
          f"prefill logits finite, shape {got.shape}")
    err = float(np.abs(got - want).max() / np.abs(want).max())
    check(err <= LOGIT_TOL, f"kernel logits vs dequantized f32 reference: "
                            f"max rel err {err:.3e} <= {LOGIT_TOL:g}")


def build(seed: int):
    from repro.launch import serve as serve_cli

    args = argparse.Namespace(arch="gemma_2b", smoke=False, seed=seed,
                              quant="msgemm", d=D)
    params, cfg, _ = serve_cli.build_model(args)
    log(f"gemma-2b: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
        f"quant={cfg.quant.mode} d={cfg.quant.d} "
        f"scale_block={cfg.quant.scale_block}")
    return params, cfg


def one_chip(seed: int) -> None:
    params, cfg = build(seed)
    reqs = make_requests(cfg.vocab_size, (64, 128, 192, 256) * 2, 32, seed)
    eng, tokens, dt = serve(params, cfg, reqs, slots=4)
    check_engine(eng, cfg)
    n = sum(len(t) for t in tokens.values())
    log(f"smoke figure, not a metric: {n} tokens for {len(reqs)} requests "
        f"in {dt:.1f} s wall, compilation included")
    static_parity(params, cfg, reqs, tokens)
    logit_check(params, cfg, reqs[0].prompt)


def four_chips(seed: int) -> None:
    import jax

    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices visible")
    params, cfg = build(seed)
    reqs = make_requests(cfg.vocab_size, (64, 128) * 2, 16, seed)
    mesh = make_mesh((4,), ("model",))
    eng, sharded, dt = serve(params, cfg, reqs, slots=4, mesh=mesh)
    plans = list(eng.exec_plans.values())
    n_sh = sum(p.shard is not None for p in plans)
    check(bool(plans) and n_sh > 0,
          f"{n_sh}/{len(plans)} mesh plans sharded over model=4")
    check_engine(eng, cfg)
    log(f"smoke figure, not a metric: model=4 engine {dt:.1f} s wall")
    with jax.default_device(jax.devices()[0]):
        _, single, dt = serve(params, cfg, reqs, slots=4)
    log(f"smoke figure, not a metric: one-chip engine {dt:.1f} s wall")
    check(sharded == single,
          "model=4 tokens identical to the one-chip engine's")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"FAIL: needs a TPU; JAX's first device is {dev.platform!r} "
            f"({dev.device_kind})")
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        log(f"FAIL: the repro package is not beside chip_smoke.py "
            f"({ROOT / 'src' / 'repro'})")
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro import dispatch
    from repro.launch.cache import use_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {use_compile_cache()}")
    # plans come from the heuristic alone: point the plan cache at a file
    # that does not exist
    plan_cache = ROOT / ".jax_cache" / "no-plans.json"
    check(not plan_cache.exists(), f"no plan-cache file at {plan_cache}")
    dispatch.set_cache_path(plan_cache)

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(args.seed)
    log(f"compile: {sum(compile_s):.1f} s over {len(compile_s)} programs; "
        f"run {time.perf_counter() - t0:.1f} s wall")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"peak HBM {d.id}: "
            f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
