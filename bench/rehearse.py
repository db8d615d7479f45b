"""Rehearse every cell without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--skip-compile]

1. Each cell of ``BENCHMARK.json`` runs end to end on the CPU at a tiny
   size of its own configuration (one period of its block pattern,
   hidden 72), on the backend the CPU selects, once untraced and once
   traced.
2. Each cell's prefill and decode step is compiled at its full size for
   a described TPU v5e, on the backend selection picks for a TPU, and
   the compiler's memory analysis is printed: a tiling, VMEM or memory
   refusal shows here, not on the chip.  A cell on several chips is
   compiled over a ``model`` mesh of that many described v5e chips, with
   the serving engine's sharding rules.

A script run by hand before a chip call, not a test.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY = {"hidden_size": 72, "num_attention_heads": 4, "head_dim": 18,
        "intermediate_size": 144, "vocab_size": 512,
        "num_hidden_layers": 2}
TINY_MIX = {"clients": 2, "requests_per_client": 4,
            "prompt": {"dist": "uniform", "min": 8, "max": 24},
            "output": {"dist": "uniform", "min": 4, "max": 12}}


def tiny_config(c: dict) -> dict:
    """The same architecture at a CPU size: GQA stays GQA, and a block
    pattern keeps one whole period."""
    out = dict(c, **TINY)
    out["num_key_value_heads"] = (TINY["num_attention_heads"]
                                  if c["num_key_value_heads"]
                                  == c["num_attention_heads"] else 2)
    period = len(c["program"].get("model", {}).get("block_pattern", ()))
    out["num_hidden_layers"] = max(period, TINY["num_hidden_layers"])
    return out


def tiny_tree(dest: Path, root: Path = ROOT) -> list[str]:
    """Copy the benchmark into ``dest`` with every configuration, mix
    and cell cut to a CPU size; returns the cell names."""
    shutil.copytree(root / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for path in (dest / "bench" / "configs").glob("*.json"):
        path.write_text(json.dumps(tiny_config(json.loads(path.read_text()))))
    for path in (dest / "bench" / "traffic").glob("*.json"):
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        **TINY_MIX)))
    for path in (dest / "bench" / "workloads").glob("*.json"):
        cell = json.loads(path.read_text())
        cell["engine"] = dict(cell["engine"], max_slots=2, max_model_len=48)
        cell["check"] = dict(cell["check"], requests=2)
        path.write_text(json.dumps(cell))
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    (dest / "src").symlink_to(root / "src")
    return [w["name"] for w in spec["workloads"]]


def rehearse_cells() -> None:
    from bench import run

    with tempfile.TemporaryDirectory() as tmp:
        names = tiny_tree(Path(tmp))
        for name in names:
            for trace in (False, True):
                t = time.perf_counter()
                out = run.run_cell(Path(tmp), name, 2**31 + 11, 1.0, trace,
                                   require_chip=False, t_start=t)
                print(f"{name} trace={int(trace)}: "
                      f"{json.dumps(out)[:2000]}", flush=True)


def compile_for_v5e(root: Path = ROOT) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from contextlib import nullcontext
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from bench import layout, model
    from repro import dispatch
    from repro.dispatch import registry
    from repro.distributed import sharding as shd
    from repro.models import transformer as T
    from repro.runtime import serve as SV

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    policy = dispatch.ExecPolicy(interpret=False)
    spec = layout.Benchmark(root)
    for w in spec.spec["workloads"]:
        cell = spec.cell(w["name"])
        cfg = model.model_config(cell.config)
        eng = cell.engine
        bs, B = eng["block_size"], eng["max_slots"]
        W = -(-eng["max_model_len"] // bs) * bs
        nb = B * (W // bs) + 1
        params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                                jax.random.PRNGKey(0))
        pool = jax.eval_shape(lambda: T.init_paged_cache(
            cfg, nb, bs, model.kv_dtype(cell.config)))
        if cell.chips > 1:
            mesh = Mesh(np.asarray(topo.devices[:cell.chips]), ("model",),
                        axis_types=(AxisType.Auto,))
            rep = NamedSharding(mesh, PartitionSpec())
            p_sh = shd.shardings(params, mesh, model.SERVE_RULES)
            k_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                shd.paged_cache_specs(pool, mesh, model.SERVE_RULES),
                is_leaf=lambda s: isinstance(s, PartitionSpec))
            scope = lambda: shd.use(mesh, model.SERVE_RULES)  # noqa: E731
        else:
            rep = SingleDeviceSharding(topo.devices[0])
            p_sh = jax.tree.map(lambda _: rep, params)
            k_sh = jax.tree.map(lambda _: rep, pool)
            scope = nullcontext
        place = lambda t, sh: jax.tree.map(  # noqa: E731
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            t, sh)
        params, pool = place(params, p_sh), place(pool, k_sh)

        def step(params, pool, tokens, positions, ws, vs, last):
            logits, pool = SV.paged_step(params, cfg, tokens, pool,
                                         positions, ws, vs, last)
            return jnp.argmax(logits, -1), pool

        for label, (nb_, nt) in (("prefill", (1, 16)), ("decode", (B, 1))):
            ints = [jax.ShapeDtypeStruct(s, np.int32, sharding=rep)
                    for s in ((nb_, nt), (nb_, nt), (nb_, nt), (nb_, W),
                              (nb_,))]
            t = time.perf_counter()
            # backend selection as on the chip
            with mock.patch.object(registry, "device_kind", lambda: "tpu"), \
                    scope(), dispatch.using_policy(policy):
                compiled = jax.jit(step, donate_argnums=(1,)).lower(
                    params, pool, *ints).compile()
            backends = sorted(set(re.findall(
                r"%(msgemm_\w+?)(?:\.\d+)? = ", compiled.as_text())))
            mem = compiled.memory_analysis()
            print(f"{cell.name} {label} on {cell.chips} chip(s), linear "
                  f"kernels {backends}: compiled for v5e in "
                  f"{time.perf_counter() - t:.1f} s; arguments "
                  f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temps "
                  f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, outputs "
                  f"{mem.output_size_in_bytes / 2**30:.2f} GiB", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--skip-compile", action="store_true")
    ap.add_argument("--skip-cells", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    if not args.skip_cells:
        rehearse_cells()
    if not args.skip_compile:
        compile_for_v5e()
    return 0


if __name__ == "__main__":
    sys.exit(main())
