"""Kernel-level microbenchmark: the reordered produce-amortized msgemm
kernel vs the legacy formulation, plus fused-vs-unfused epilogues.

Emits ``benchmarks/results/BENCH_kernels.json`` so the repo has a
kernel-level perf trajectory across PRs:

* per shape: wall time of the new kernel (``acc_in_vmem=True`` — m
  innermost, LUT produced once per (b, j) into VMEM scratch, single HBM
  writeback) vs the legacy kernel (j innermost, produce re-run every
  m-tile, ``y_ref +=`` per step), and the **produce-amortization
  factor** — the number of m-tiles sharing one produce, i.e. how many
  times the legacy grid re-computed the LUT dot;
* per shape: the fused epilogue (gelu + residual inside the final
  writeback) vs the same kernel plus separate jnp elementwise ops (what
  model code used to issue);
* a **parity gate**: on exactly representable inputs the new kernel's
  identity-epilogue output must be bit-identical to ``kernels/ref.py`` —
  the process exits non-zero if it is not (CI fails the job).

Run::

    PYTHONPATH=src python benchmarks/kernel_microbench.py --smoke

``--smoke`` uses the small shape set + 2 reps (the CI configuration);
the default set adds larger shapes for real-hardware runs.

``--backends`` (TPU only) instead times each msGeMM-mode linear backend
at every (m, k) linear of the benchmark's configurations: one call of
``msgemm_pallas`` and ``msgemm_mxu`` exactly as a model step runs it
(``dispatch.execute`` with the backend forced, so a backend's per-call
work around its kernel is counted), a dense bf16 matmul of the same
shape as the MXU's reference point, and each backend's error against
the dequantized float32 product.  Rows above 16 (prefill) time only
``msgemm_mxu`` and dense, at the vocab-sized heads::

    PYTHONPATH=src python benchmarks/kernel_microbench.py --backends \
        --rows 8,16,128 --out linear_backends.json
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

RESULTS = Path(__file__).parent / "results"

# BENCH_kernels.json schema history:
#   (unversioned) — PR 4: per-shape new/legacy/epilogue timings + parity
#   2 — PR 6: adds schema_version, and per shape a "roofline" block
#       (produce/consume op split, bytes moved, attainable_s,
#       roofline_fraction, hardware model) from the obs.costs model
BENCH_KERNELS_SCHEMA = 2

# name, d, scale_block, m, k, b — decode shapes are the tall-skinny
# (large-m, small-b) cells where the legacy grid's produce re-computation
# dominated; prefill is the wide-batch sanity cell.
SMOKE_SHAPES = [
    ("decode_m2048_k768_b8", 3, 12, 2048, 768, 8),
    ("decode_m2048_k768_b1", 3, 12, 2048, 768, 1),
    ("decode_m4096_k768_b8", 3, 12, 4096, 768, 8),
    ("prefill_m512_k768_b128", 3, 12, 512, 768, 128),
]
FULL_SHAPES = SMOKE_SHAPES + [
    ("decode_m8192_k1024_b8", 3, 12, 8192, 1024, 8),
    ("prefill_m2048_k2048_b256", 3, 12, 2048, 2048, 256),
]


# (configuration, linear, m, k) of bench/configs/*.json, and the heads
# that a prefill of more than 16 rows is timed at
CELL_LINEARS = [
    ("starcoder2", "wq/wo", 6144, 6144), ("starcoder2", "wk/wv", 512, 6144),
    ("starcoder2", "up", 24576, 6144), ("starcoder2", "down", 6144, 24576),
    ("starcoder2", "lm_head", 49152, 6144),
    ("phi3", "wq/wk/wv/wo", 3072, 3072), ("phi3", "gate/up", 8192, 3072),
    ("phi3", "down", 3072, 8192), ("phi3", "lm_head", 32064, 3072),
]
BACKENDS = ("msgemm_pallas", "msgemm_mxu")


def _bench(fn, reps: int, calls: int = 1) -> float:
    """Best over ``reps`` of the mean time of ``calls`` calls in a row."""
    import jax

    jax.block_until_ready(fn())  # compile + warm
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        for _ in range(calls):
            y = fn()
        jax.block_until_ready(y)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best


def backend_row(m: int, k: int, rows: int, key) -> dict:
    """One linear shape: each backend's and dense bf16's time per call
    (ms), and each backend's max error over the dense f32 product's
    largest magnitude."""
    import jax
    import jax.numpy as jnp

    from repro import dispatch
    from repro.core import linear
    from repro.core.spec import QuantSpec

    spec = QuantSpec(mode="msgemm", d=3, scale_block=36)
    kw, kx = jax.random.split(key)
    params = jax.jit(lambda key: linear.init(key, k, m, spec))(kw)
    x = jax.random.normal(kx, (rows, k), jnp.float32).astype(jnp.bfloat16)

    def run(name):
        pol = dispatch.ExecPolicy(backend=name, interpret=False)
        return jax.jit(lambda p, x: dispatch.execute(
            p, x, spec, in_dim=k, policy=pol))

    want = run("dense_fallback")(params, x.astype(jnp.float32))
    scale = float(jnp.max(jnp.abs(want)))
    row = {"m": m, "k": k, "rows": rows}
    for name in BACKENDS if rows <= 16 else BACKENDS[1:]:
        fn = run(name)
        got = fn(params, x).astype(jnp.float32)
        row[f"{name}_ms"] = 1e3 * _bench(
            lambda: fn(params, x), 3,
            calls=3 if name == "msgemm_pallas" else 20)
        row[f"{name}_rel_err"] = float(jnp.max(jnp.abs(got - want))) / scale
    w = jax.random.normal(kw, (m, k), jnp.bfloat16)
    dense = jax.jit(lambda w, x: x @ w.T)
    row["dense_bf16_ms"] = 1e3 * _bench(lambda: dense(w, x), 3, calls=20)
    return row


def backends(rows, out: Path | None) -> list:
    """:func:`backend_row` at every cell linear and row count (rows above
    16 at the heads only), one JSON line each."""
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(f"--backends needs a TPU, found "
                         f"{jax.default_backend()!r}")
    result = []
    key = jax.random.PRNGKey(0)
    for cfg, tag, m, k in CELL_LINEARS:
        for b in rows:
            if b > 16 and tag != "lm_head":
                continue
            key, sub = jax.random.split(key)
            row = {"config": cfg, "linear": tag, **backend_row(m, k, b, sub),
                   "device_kind": jax.devices()[0].device_kind}
            print(json.dumps(row), flush=True)
            result.append(row)
    if out is not None:
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(result, indent=1) + "\n")
    return result


def _parity_bitexact(d: int, sb: int, m: int, k: int, b: int) -> bool:
    """Identity-epilogue bit-identity vs kernels/ref.py on exactly
    representable inputs (every sum/product exact -> codegen-ulp-free)."""
    import jax.numpy as jnp

    from repro.core import packing
    from repro.kernels import ops, ref

    rng = np.random.default_rng(m + k + b)
    codes = jnp.asarray(rng.integers(0, 16, size=(m, k)), jnp.uint8)
    x = jnp.asarray(rng.integers(-4, 5, size=(k, b)), jnp.float32)
    sc = jnp.asarray(2.0 ** rng.integers(-2, 3, size=(m, -(-k // sb))),
                     jnp.float32)
    got = np.asarray(ops.msgemm(codes, x, d, scales=sc, scale_block=sb))
    want = np.asarray(ref.msgemm_ref(packing.pack_indices(codes, d), x, sc,
                                     d=d, scale_block=sb))
    return bool(np.array_equal(got, want))


def run(shapes=None, reps: int = 2) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.epilogue import Epilogue
    from repro.kernels import ops
    from repro.kernels.mode import resolve_interpret

    shapes = shapes or SMOKE_SHAPES
    rng = np.random.default_rng(0)
    rows = []
    for name, d, sb, m, k, b in shapes:
        codes = jnp.asarray(rng.integers(0, 16, size=(m, k)), jnp.uint8)
        x = jnp.asarray(rng.standard_normal((k, b)), jnp.float32)
        sc = jnp.asarray(
            np.abs(rng.standard_normal((m, -(-k // sb)))) + 0.1, jnp.float32)
        tm, tj, tb = ops.msgemm_tiles(m, -(-k // d), b, d, sb)
        amort = -(-m // tm)  # m-tiles sharing one produce

        # every timed closure is one jitted program, so the comparison
        # measures the kernels — not eager pad/dispatch overhead
        t_new = _bench(jax.jit(lambda: ops.msgemm(
            codes, x, d, scales=sc, scale_block=sb)), reps)
        t_old = _bench(jax.jit(lambda: ops.msgemm(
            codes, x, d, scales=sc, scale_block=sb, acc_in_vmem=False)),
            reps)

        ep = Epilogue(act="gelu", residual=True)
        res = jnp.asarray(rng.standard_normal((m, b)), jnp.float32)
        t_fused = _bench(jax.jit(lambda: ops.msgemm(
            codes, x, d, scales=sc, scale_block=sb, epilogue=ep,
            residual=res)), reps)

        # fair baseline: the old model-side elementwise tail inside one
        # jit with the kernel call, exactly like pre-overhaul model code
        @jax.jit
        def unfused():
            y = ops.msgemm(codes, x, d, scales=sc, scale_block=sb)
            return jax.nn.gelu(y) + res

        t_unfused = _bench(unfused, reps)
        parity = _parity_bitexact(d, sb, m, k, b)
        from repro.obs import costs

        ann = costs.annotate(t_new, m, k, b, quant="msgemm", d=d)
        roofline = {f: ann[f] for f in
                    ("produce_flops", "consume_ops", "flops", "bytes",
                     "attainable_s", "roofline_fraction", "hardware")}
        rows.append({
            "shape": name, "d": d, "scale_block": sb, "m": m, "k": k, "b": b,
            "tiles": {"tm": tm, "tj": tj, "tb": tb},
            "produce_amortization_factor": amort,
            "new_kernel_s": t_new, "legacy_kernel_s": t_old,
            "speedup_new_vs_legacy": t_old / t_new,
            "epilogue_fused_s": t_fused, "epilogue_unfused_s": t_unfused,
            "epilogue_fusion_speedup": t_unfused / t_fused,
            "identity_parity_bitexact_vs_ref": parity,
            "roofline": roofline,
        })
        print(f"[kernels] {name}: amort={amort} "
              f"new={t_new * 1e3:.1f}ms legacy={t_old * 1e3:.1f}ms "
              f"({t_old / t_new:.2f}x) epilogue fused/unfused="
              f"{t_unfused / t_fused:.2f}x "
              f"roofline={roofline['roofline_fraction']:.3g} "
              f"parity={'OK' if parity else 'FAIL'}")

    decode = [r for r in rows if r["shape"].startswith("decode")]
    out = {
        "schema_version": BENCH_KERNELS_SCHEMA,
        "device": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "interpret": resolve_interpret(None),
        "reps": reps,
        "shapes": rows,
        "all_new_beat_legacy": all(
            r["speedup_new_vs_legacy"] > 1.0 for r in rows),
        "decode_min_speedup": min(
            (r["speedup_new_vs_legacy"] for r in decode), default=None),
        "parity_all_bitexact": all(
            r["identity_parity_bitexact_vs_ref"] for r in rows),
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small shape set + 2 reps (the CI configuration)")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: "
                         "benchmarks/results/BENCH_kernels.json)")
    ap.add_argument("--backends", action="store_true",
                    help="time the msGeMM backends at the benchmark's "
                         "linear shapes instead (TPU only)")
    ap.add_argument("--rows", default="8,16",
                    help="row counts for --backends")
    args = ap.parse_args(argv)
    if args.backends:
        backends([int(r) for r in args.rows.split(",")],
                 Path(args.out) if args.out else None)
        return 0
    shapes = SMOKE_SHAPES if args.smoke else FULL_SHAPES
    reps = args.reps if args.reps is not None else (2 if args.smoke else 3)
    out = run(shapes=shapes, reps=reps)
    path = Path(args.out) if args.out else RESULTS / "BENCH_kernels.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(f"[kernels] wrote {path}")
    if not out["parity_all_bitexact"]:
        print("[kernels] FAIL: identity-epilogue parity vs kernels/ref.py "
              "regressed")
        return 1
    if not out["all_new_beat_legacy"]:
        print("[kernels] WARNING: reordered kernel lost to legacy on some "
              "shape (see JSON)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
