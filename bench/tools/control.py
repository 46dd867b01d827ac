"""Readings that a cell's ``correct`` limit is set from, in one process:
for each seed a full run of the cell (build, warm, window, compare) with
the int8 control read beside the program on the same sampled requests
and judged by the same rows and limits; or, with ``--fault``, runs with
a fault of ``bench/faults.py`` planted in the timed path.

  python bench/tools/control.py --workload granite-3-8b-l8.decode \\
      --seeds 11,12,13 --seconds 20
  python bench/tools/control.py --workload granite-3-8b-l8.decode \\
      --seeds 14 --seconds 20 --fault state_unchanged,token_altered

One JSON line per run. Exits 1 when a sound run is not correct, or when
the control or a planted fault is.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fault", default="",
                    help="comma-separated names from bench.faults.FAULTS")
    args = ap.parse_args()
    from bench import faults, harness
    spec = harness.load_spec(ROOT)
    cell = harness.find_cell(spec, args.workload)
    devices = harness.require_chips(cell["chips"])
    if devices is None:
        return 3
    planted = [f for f in args.fault.split(",") if f]
    bad = 0
    for name in planted or [None]:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.perf_counter()
            res = harness.run_cell(
                spec, cell, seed, args.seconds, False, t_start=t0,
                devices=devices, control=name is None,
                patch=faults.FAULTS[name] if name else None)
            line = {"seed": seed, "fault": name, "correct": res["correct"],
                    "compared": res["compared"], "metrics": res["metrics"],
                    "run_s": time.perf_counter() - t0}
            if name is None:
                line["control"] = res["control"]
                bad += (not res["correct"]) + res["control"]["correct"]
            else:
                bad += res["correct"]
            print(json.dumps(line), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
