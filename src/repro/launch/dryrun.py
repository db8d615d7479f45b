import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes — 16x16 (single pod, 256 chips) and
2x16x16 (two pods, 512 chips) — and record memory/cost/collective
artifacts for the roofline analysis (EXPERIMENTS.md §Dry-run/§Roofline).

No tensor is ever allocated at full scale: inputs and state are
ShapeDtypeStructs; the deliverable is that ``.lower().compile()``
succeeds (sharding coherent, memory fits) for all cells.

Usage:
    python -m repro.launch.dryrun --arch gemma_2b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both
    python -m repro.launch.dryrun --all --mesh single --quant msgemm
"""

import argparse
import functools
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs import shapes as shp
from repro.core.spec import QuantSpec
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh, mesh_devices
from repro.models import transformer as T
from repro.optim import AdamWConfig
from repro.runtime import train as RT

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results/dryrun")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# Per-arch train-cell memory policy (DESIGN.md §4: 2.4TB llama4 train state).
TRAIN_OVERRIDES = {
    "llama4_maverick": {"param_dtype": "bfloat16", "opt_dtype": "bfloat16",
                        "grad_dtype": "bfloat16", "microbatches": 8},
    "jamba_v01": {"microbatches": 8},
}
_SHAPE_RE = re.compile(
    r"(\w+)\[([\d,]*)\].* (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def parse_collectives(hlo_text: str) -> dict:
    """Inventory of collective ops: per kind, op count + result bytes
    (per-device partitioned shapes, scan bodies counted once — the
    analytic roofline model supplies trip-count weighting)."""
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _SHAPE_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if line.startswith("ROOT"):
            line = line[5:]
        # only count the defining op, not operands
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        for d in dims.split(","):
            if d:
                nbytes *= int(d)
        out[kind]["count"] += 1
        out[kind]["bytes"] += nbytes
    return out


def serve_quant_config(mode: str, d=None) -> QuantSpec:
    if mode == "bf16":
        return QuantSpec(mode="bf16")
    env_d = os.environ.get("DRYRUN_D", "3")  # §Perf B/C lever
    d = d or ("adaptive" if env_d == "adaptive" else int(env_d))
    storage = os.environ.get("DRYRUN_STORAGE", "packed_idx")
    return QuantSpec(mode=mode, d=d,
                     scale_block=12 if d == "adaptive" else 12 * d,
                     storage=storage)


def _key_sds():
    return jax.eval_shape(lambda: jax.random.PRNGKey(0))


def build_cell(arch: str, shape_name: str, quant: str):
    """Returns (fn, args_sds, in_specs_builder, label) for the cell."""
    base = configs.get_config(arch)
    shape = shp.SHAPES[shape_name]
    ok, reason = shp.applicable(base, shape_name)
    if not ok:
        return None, reason

    if shape.kind == "train":
        cfg = base  # training is bf16-dense (quantized weights don't train)
        # gradient accumulation keeps per-microbatch activations (incl. the
        # (tokens, vocab) logits block) inside v5e HBM; 4 microbatches
        # => 64k tokens per microbatch at train_4k.  The 400B MoE also
        # needs bf16 params + bf16 Adam state to fit 256 chips (2.4 TB
        # train state; f32 Adam alone would be 4.8 TB > 4 TB pod HBM).
        ov = TRAIN_OVERRIDES.get(arch, {})
        cfg = cfg.replace(**{k: v for k, v in ov.items()
                             if k in ("param_dtype",)})
        if os.environ.get("DRYRUN_INT8_GATHER"):  # §Perf A lever
            cfg = cfg.replace(fsdp_int8_gather=True)
        if os.environ.get("DRYRUN_SAVE_GATHERED"):  # §Perf A lever
            cfg = cfg.replace(save_gathered_weights=True)
        if os.environ.get("DRYRUN_REMAT_POLICY"):  # §Perf A4 lever
            cfg = cfg.replace(
                remat_policy=os.environ["DRYRUN_REMAT_POLICY"])
        tcfg = RT.TrainConfig(
            optimizer=AdamWConfig(state_dtype=ov.get("opt_dtype", "float32")),
            grad_accum_dtype=ov.get("grad_dtype", "float32"),
            microbatches=int(os.environ.get(
                "DRYRUN_MICROBATCHES", str(ov.get("microbatches", 4)))))
        state_sds = jax.eval_shape(
            functools.partial(RT.init_state, cfg=cfg, tcfg=tcfg), _key_sds())
        batch_sds = shp.input_specs(cfg, shape_name)
        fn = functools.partial(RT.train_step, cfg=cfg, tcfg=tcfg)

        def specs(mesh, rules):
            st = shd.param_specs(state_sds, mesh, rules)
            bt = shd.batch_specs(batch_sds, mesh, rules)
            return (st, bt), (st, None)

        return (fn, (state_sds, batch_sds), specs, cfg), None

    qc = serve_quant_config(quant)
    cfg = base.replace(quant=qc) if quant != "bf16" else base
    params_sds = jax.eval_shape(
        functools.partial(T.init_params, cfg=cfg), _key_sds())
    inputs = shp.input_specs(cfg, shape_name)

    if shape.kind == "prefill":
        cache = None  # prefill cell lowers the forward over the prompt
        fn = functools.partial(_prefill_forward, cfg=cfg)
        args = (params_sds, inputs)

        def specs(mesh, rules):
            ps = shd.param_specs(params_sds, mesh, rules)
            bs = shd.batch_specs(inputs, mesh, rules)
            return (ps, bs), None

        return (fn, args, specs, cfg), None

    # decode: one token against a seq_len-deep cache
    cache_dt = {"bf16": jnp.bfloat16, "f32": jnp.float32,
                "f8": jnp.float8_e4m3fn}[
        os.environ.get("DRYRUN_CACHE_DTYPE", "bf16")]  # §Perf B lever
    inputs = shp.input_specs(cfg, shape_name, cache_dtype=cache_dt) \
        if shape.kind == "decode" else inputs
    fn = functools.partial(_decode, cfg=cfg)
    args = (params_sds, inputs["token"], inputs["cache"], inputs["pos"])

    def specs(mesh, rules):
        ps = shd.param_specs(params_sds, mesh, rules)
        cs = shd.cache_specs(inputs["cache"], mesh, rules)
        ts = shd.batch_specs({"token": inputs["token"]}, mesh, rules)["token"]
        pos_s = ts
        logits_s = None
        return (ps, ts, cs, pos_s), (logits_s, cs)

    return (fn, args, specs, cfg), None


def _prefill_forward(params, batch, *, cfg):
    logits, _ = T.forward(params, cfg, batch, mode="prefill")
    return logits[:, -1]


def _decode(params, token, cache, pos, *, cfg):
    return T.decode_step(params, cfg, token, cache, pos)


def to_shardings(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s if s is not None else P()),
        spec_tree, is_leaf=lambda s: isinstance(s, P) or s is None)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, quant: str,
             rules: str = "default", verbose: bool = True) -> dict:
    label = f"{arch}/{shape_name}/{'multi' if multi_pod else 'single'}/{quant}"
    built, reason = build_cell(arch, shape_name, quant)
    if built is None:
        return {"cell": label, "status": "skipped", "reason": reason}
    fn, args, specs_builder, cfg = built
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = shp.SHAPES[shape_name]
    t0 = time.time()
    with shd.use(mesh, rules):
        in_specs, out_specs = specs_builder(mesh, rules)
        # donate the train state / decode cache (standard in-place update;
        # without it memory_analysis double-counts state as out + temps)
        donate = {"train": (0,), "prefill": (), "decode": (2,)}[shape.kind]
        jf = jax.jit(fn, in_shardings=to_shardings(mesh, in_specs),
                     out_shardings=(to_shardings(mesh, out_specs)
                                    if out_specs is not None else None),
                     donate_argnums=donate)
        lowered = jf.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    n_dev = mesh_devices(mesh)
    result = {
        "cell": label,
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "quant": quant,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_device": ma.argument_size_in_bytes,
            "output_bytes_per_device": ma.output_size_in_bytes,
            "temp_bytes_per_device": ma.temp_size_in_bytes,
            "code_bytes": ma.generated_code_size_in_bytes,
            "total_per_device_gb": round(
                (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 2**30,
                3),
        },
        "cost_analysis": {
            "flops_per_device_hlo": ca.get("flops", 0.0),
            "bytes_accessed_per_device_hlo": ca.get("bytes accessed", 0.0),
        },
        "collectives": coll,
    }
    if verbose:
        print(f"[dryrun] {label}: compile={t_compile:.1f}s "
              f"mem/dev={result['memory']['total_per_device_gb']}GB")
        print(f"[dryrun]   memory_analysis: {ma}")
        print(f"[dryrun]   cost_analysis: flops={ca.get('flops')} "
              f"bytes={ca.get('bytes accessed')}")
        print(f"[dryrun]   collectives: "
              + ", ".join(f"{k}:{v['count']}({v['bytes']/2**20:.1f}MiB)"
                          for k, v in coll.items() if v["count"]))
    return result


def save_result(res: dict) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = res["cell"].replace("/", "__") + ".json"
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    return path


def default_quant_for(shape_name: str, quant_arg: str) -> str:
    if quant_arg != "auto":
        return quant_arg
    # serve cells default to the paper's target (msgemm int4); train is bf16
    return "bf16" if shape_name == "train_4k" else "msgemm"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--quant", default="auto",
                    choices=["auto", "bf16", "msgemm", "int4_dequant"])
    ap.add_argument("--rules", default="default")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = configs.ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(shp.SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                quant = default_quant_for(shape_name, args.quant)
                label = (f"{arch}__{shape_name}__"
                         f"{'multi' if multi else 'single'}__{quant}.json")
                path = os.path.join(RESULTS_DIR, label)
                if os.path.exists(path) and not args.force:
                    print(f"[dryrun] cached: {label}")
                    continue
                try:
                    res = run_cell(arch, shape_name, multi_pod=multi,
                                   quant=quant, rules=args.rules)
                except Exception as e:  # a failure here is a system bug
                    traceback.print_exc()
                    res = {"cell": label, "status": "failed",
                           "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                save_result(res)
                results.append(res)
    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    print(f"[dryrun] done: {ok} ok, {sk} skipped, {failures} FAILED")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
