"""Rehearse every cell without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--skip-compile]

1. Each cell of ``BENCHMARK.json`` runs end to end on the CPU at a tiny
   size of its own configuration (2 layers, hidden 72), with the msGeMM
   Pallas kernel in interpret mode, once untraced and once traced.
2. Each configuration's prefill and decode step is compiled at its full
   size for a described TPU v5e, and the compiler's memory analysis is
   printed: a tiling, VMEM or memory refusal shows here, not on the chip.

A script run by hand before a chip call, not a test.
"""

from __future__ import annotations

import argparse
import json
import os
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
    """The same architecture at a CPU size: GQA stays GQA."""
    out = dict(c, **TINY)
    out["num_key_value_heads"] = (TINY["num_attention_heads"]
                                  if c["num_key_value_heads"]
                                  == c["num_attention_heads"] else 2)
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
    from repro import dispatch

    # the chip's backend, in interpret mode on the CPU
    dispatch.set_default_policy(dispatch.ExecPolicy(backend="msgemm_pallas"))
    with tempfile.TemporaryDirectory() as tmp:
        names = tiny_tree(Path(tmp))
        for name in names:
            for trace in (False, True):
                t = time.perf_counter()
                out = run.run_cell(Path(tmp), name, 2**31 + 11, 1.0, trace,
                                   require_chip=False, t_start=t)
                print(f"{name} trace={int(trace)}: "
                      f"{json.dumps(out)[:2000]}", flush=True)


def compile_for_v5e() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import layout, model
    from repro import dispatch
    from repro.models import transformer as T
    from repro.runtime import serve as SV

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    policy = dispatch.ExecPolicy(backend="msgemm_pallas", interpret=False)
    spec = layout.Benchmark(ROOT)
    for w in spec.spec["workloads"]:
        cell = spec.cell(w["name"])
        cfg = model.model_config(cell.config)
        eng = cell.engine
        bs, B = eng["block_size"], eng["max_slots"]
        W = -(-eng["max_model_len"] // bs) * bs
        nb = B * (W // bs) + 1
        place = lambda t: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
        params = place(jax.eval_shape(lambda k: T.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
        pool = place(jax.eval_shape(lambda: SV.init_paged_cache(
            cfg, nb, bs, model.kv_dtype(cell.config))))

        def step(params, pool, tokens, positions, ws, vs, last):
            logits, pool = SV.paged_step(params, cfg, tokens, pool,
                                         positions, ws, vs, last)
            return jnp.argmax(logits, -1), pool

        for label, (nb_, nt) in (("prefill", (1, 16)), ("decode", (B, 1))):
            ints = [jax.ShapeDtypeStruct(s, np.int32, sharding=one)
                    for s in ((nb_, nt), (nb_, nt), (nb_, nt), (nb_, W),
                              (nb_,))]
            t = time.perf_counter()
            with dispatch.using_policy(policy):
                compiled = jax.jit(step, donate_argnums=(1,)).lower(
                    params, pool, *ints).compile()
            mem = compiled.memory_analysis()
            print(f"{cell.name} {label}: compiled for v5e in "
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
