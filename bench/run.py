"""Run one benchmark cell once and print its result line.

  python bench/run.py --workload <config>.<traffic> --seed <n> \\
      --seconds <run_seconds> --trace <0|1>

The cell's parts are files found by name: ``BENCHMARK.json`` at the
checkout's root names the cell's configuration and traffic;
``bench/configs/<config>.json`` holds the configuration as it is run,
``bench/traffic/<traffic>.json`` the mix, and
``bench/metrics/<metric>.py`` the reader of each metric.

The program under test is built from the seed (weights made on the
device in one jitted call), warmed on every shape the cell's traffic
uses, then driven for ``--seconds``. With ``--trace 0`` the result line
carries the cell's end-to-end metrics; with ``--trace 1`` a profiler
trace is taken over part of the window and the line carries the cell's
per-layer metrics. Either way the served tokens of a sample of the served
requests, one from every slot, are compared with a plain float32 reference afterwards, and
``correct`` says whether each compared number kept its limit. Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness
    try:
        spec = harness.load_spec(ROOT)
        cell = harness.find_cell(spec, args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    devices = harness.require_chips(cell["chips"])
    if devices is None:
        return 3
    result = harness.run_cell(spec, cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              devices=devices)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
