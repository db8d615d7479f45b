"""Training entry point.

    PYTHONPATH=src python -m repro.launch.train --arch gemma_2b --smoke \
        --steps 100 --mesh 1x1 --checkpoint-dir /tmp/ckpt

On a real fleet the same module runs under the production mesh
(--mesh 16x16 / 2x16x16); on this host use --mesh 1x1 or set
XLA_FLAGS=--xla_force_host_platform_device_count=N first.
"""

from __future__ import annotations

import argparse
import functools

import jax

from repro import configs
from repro.data import DataConfig, SyntheticStream
from repro.distributed import sharding as shd
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.optim import AdamWConfig, schedules
from repro.runtime import train as RT
from repro.runtime.driver import DriverConfig, run


def parse_mesh(s: str):
    dims = tuple(int(x) for x in s.split("x"))
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, axes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    mesh = parse_mesh(args.mesh)
    tcfg = RT.TrainConfig(
        optimizer=AdamWConfig(
            lr=schedules.warmup_cosine(args.lr, 10, args.steps)),
        microbatches=args.microbatches)
    data = SyntheticStream(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len + 1,
        global_batch=args.global_batch, seed=args.seed,
        frontend=cfg.frontend, d_model=cfg.d_model,
        num_frames=max(args.seq_len // 2, 8), num_patches=cfg.num_patches))

    with shd.use(mesh, cfg.logical_rules):
        state = RT.init_state(jax.random.PRNGKey(args.seed), cfg, tcfg)
        state_sh = shd.shardings(jax.eval_shape(lambda: state), mesh,
                                 cfg.logical_rules)
        state = jax.tree.map(lambda x, s: jax.device_put(x, s), state,
                             state_sh)
        step_fn = jax.jit(
            functools.partial(RT.train_step, cfg=cfg, tcfg=tcfg),
            in_shardings=(state_sh, None), out_shardings=(state_sh, None),
            donate_argnums=(0,))
        res = run(state, step_fn, data,
                  DriverConfig(total_steps=args.steps,
                               checkpoint_every=args.checkpoint_every,
                               checkpoint_dir=args.checkpoint_dir),
                  shardings=state_sh)
    print(f"final loss: {res['metrics'][-1]['loss']:.4f} "
          f"(resumed_at={res['resumed_at']})")
    return res


if __name__ == "__main__":
    main()
