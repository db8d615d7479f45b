"""Readings that set a cell's limit: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds <a,b,...> --seconds <s>

For each seed, in one process, one run of the cell as ``bench/run.py``
makes it, with a window of ``--seconds`` at the cell's own load.  After
it, the served tokens are compared with the float32 reference, and so
is the control: the same reference with every linear's input rounded to
float8 (e4m3), the precision step below the configuration's bfloat16,
judged by the same comparison against the same limit.  Each seed prints
one JSON line with both verdicts; a sound limit passes the program and
fails the control.  The lower reading of a limit is the largest program
gap over a dozen seeds, the upper the smallest control gap.  Needs the
chip, like ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = run.run_cell(ROOT, args.workload, seed, args.seconds,
                               False, t_start=time.perf_counter(),
                               control=True)
        except run.Refused as e:
            run.log(f"refused: {e}")
            return 2
        print(json.dumps({
            "seed": seed,
            "program": {"correct": out["correct"], **out["check"]},
            "control": out["control"],
            "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
