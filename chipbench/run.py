#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

from the root of a checkout.  The cell comes from ``BENCHMARK.json``.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled span of the window.  The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``check``: each number compared beside its limit); the last
lines of standard error repeat the check.  Without a TPU, with fewer
chips than the cell asks for, or without the program beside the
benchmark, it prints no result and exits 2.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the benchmark's modules import as ``chipbench.*``; its own directory
# must not shadow the standard library (``trace``)
sys.path[0] = str(ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        cell = harness.resolve(ROOT, args.workload)
        result = harness.run(ROOT, cell, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START, log=log)
    except harness.Refused as e:
        log(f"chipbench: {e}")
        return 2
    for name, c in result["check"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
