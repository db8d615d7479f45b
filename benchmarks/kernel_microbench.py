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


def _bench(fn, reps: int) -> float:
    import jax

    jax.block_until_ready(fn())  # compile + warm
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


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
    args = ap.parse_args(argv)
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
