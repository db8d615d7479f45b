"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found
by name (``bench/README.md`` says where).  Set-up builds the model from
the seed, warms the cell's two step shapes, and sends every client's
first request through prefill; then the window runs for ``--seconds``.
With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window.  After the window the served tokens of a sample of requests
are compared with a float32 reference (``bench/check.py``).

The last line of standard output is one JSON object.  Where JAX finds no
TPU, fewer chips than the cell asks for, a chip with no published peaks,
or no program beside the benchmark, the run exits non-zero and prints
no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, layout, work  # noqa: E402


class Refused(Exception):
    """The run cannot be made here; no result is printed."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info(chips: int, *, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_chip:
        if dev.platform != "tpu":
            raise Refused(f"needs a TPU; JAX's first device is "
                          f"{dev.platform!r} ({dev.device_kind})")
        if len(devs) < chips:
            raise Refused(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devs)}")
        try:
            work.peaks(dev.device_kind)
        except KeyError as e:
            raise Refused(str(e)) from None
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def import_program(root: Path) -> None:
    src = root / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"no program at {src / 'repro'}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def run_cell(root: Path, name: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             t_start: float | None = None, control: bool = False) -> dict:
    """One run of cell ``name``; returns the result line's object.

    ``control`` also compares the control with the reference
    (``bench/control.py``) and puts its verdict under ``control``; the
    benchmark's own runs never do."""
    t_start = T_START if t_start is None else t_start
    spec = layout.Benchmark(root)
    cell = spec.cell(name)
    device = device_info(cell.chips, require_chip=require_chip)
    import_program(root)

    import jax

    from bench import loop as L
    from bench import model
    from bench.traffic import ClosedLoop
    from repro import dispatch
    from repro.launch.cache import use_compile_cache
    from repro.serving import Engine

    log(f"device {device['platform']} {device['kind']} x{device['count']}; "
        f"compile cache {use_compile_cache()}")
    # plans come from the shape heuristic alone
    plan_file = root / ".jax_cache" / "bench-no-plans.json"
    if plan_file.exists():
        raise Refused(f"a plan-cache file exists at {plan_file}")
    dispatch.set_cache_path(plan_file)

    params, cfg = model.build(cell.config, seed)
    engine = Engine(params, cfg, cache_dtype=model.kv_dtype(cell.config),
                    **cell.engine)
    del params
    traffic = ClosedLoop(cell.mix, cfg.vocab_size, seed)
    if traffic.max_len > engine.max_model_len:
        raise ValueError(f"mix {cell.traffic!r} needs {traffic.max_len} "
                         f"positions, the engine holds "
                         f"{engine.max_model_len}")
    drive = L.Loop(engine, traffic)
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace \
        else None
    with L.GcPauses() as pauses:
        drive.warm()
        t_fill = time.perf_counter()
        drive.fill()
        t_frozen = time.perf_counter()
        # what set-up left on the heap is kept out of every later
        # collection, as a server does once it is warm: otherwise a full
        # collection walks the compiler's objects in the middle of a step
        gc.collect()
        gc.freeze()
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir))
        w0, w1, steps, compiles = drive.window(seconds)
        if trace_dir is not None:
            jax.profiler.stop_trace()
    setup_s = w0 - t_start
    log(f"set-up {setup_s:.3f} s; window {w1 - w0:.3f} s, {len(steps)} "
        f"steps; compilations in the window: {compiles}")
    log(f"garbage collection in the fill: "
        f"{pauses.summary(t_fill, t_frozen)}; in the window: "
        f"{pauses.summary(w0, w1)}")
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)

    view = None
    if trace_dir is not None:
        from bench import trace as TR

        try:
            view = TR.TraceView.load(trace_dir, spec.kernel_patterns())
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = view.busy_s
        device["window_s"] = view.window_s

    run = layout.Run(cell=cell, setup_s=setup_s, w0=w0, w1=w1, steps=steps,
                     reqs=drive.reqs, trace=view,
                     peaks=(work.peaks(device["kind"])
                            if device["platform"] == "tpu" else None))
    metrics = spec.read_metrics(cell, run, per_layer=trace)
    # attempted: every request live at some time in the window
    in_window = [r for r in drive.reqs.values() if r.t_sent <= w1
                 and (r.status is None or max(r.stamps, default=r.t_sent)
                      >= w0)]
    failed = sum(r.status not in (None, "ok") for r in in_window)

    # free the program's state before the reference runs
    gc.unfreeze()
    del engine, drive.engine, drive
    gc.collect()
    t_ref = time.perf_counter()
    verdict, *ctl = check.compare(cell, seed, run.reqs, w0, w1,
                                  control=control)
    log(f"reference {time.perf_counter() - t_ref:.1f} s")

    out = {"correct": verdict.ok and failed == 0,
           "attempted": len(in_window), "failed": failed,
           "metrics": metrics, "device": device}
    if view is not None:
        out["breakdown"] = view.breakdown()
    for v in (*ctl, verdict):
        for line in v.lines():
            log(line)
    if ctl:
        out["control"] = {"correct": ctl[0].ok, "check": ctl[0].numbers()}
    out["check"] = verdict.numbers()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
