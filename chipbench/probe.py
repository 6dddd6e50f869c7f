#!/usr/bin/env python3
"""Read the check's numbers of a cell over many seeds, for the program
and for the control, in one process: the readings each limit is set
from (the program's largest, the control's smallest).

    python3 chipbench/probe.py --workload <cell> --seeds 1,2,3 \\
        --seconds 3 [--control-seeds 1,2,3]

Each seed sets the cell up anew (weights, inputs), runs a window at the
cell's own load, and prints one JSON line with every number the
family's ``compare`` gives, for the program's answers and, on the
control seeds, for the reference at the precision below and for each
fault the family plants in the reference (``Model.faults``, where it
has one).  Needs a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from chipbench import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    controls = {int(s) for s in args.control_seeds.split(",") if s}
    try:
        cell = harness.resolve(ROOT, args.workload)
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            session = harness.Session(ROOT, cell, seed, log=log)
            out, _ = session.window(args.seconds, False)
            session.close_program()
            row = {"seed": seed, "answers": out.answers_in_window,
                   "checked": len(out.sampled),
                   "program": session.numbers(out)}
            if seed in controls:
                row["control"] = session.numbers(out, control=True)
                model = session.model
                if hasattr(model, "faults") and out.sampled:
                    xs = np.stack([session.pool[i] for i, _ in out.sampled])
                    want = model.reference(xs)
                    row["faults"] = {name: model.compare(got, want, xs)
                                     for name, got in model.faults(xs).items()}
            row["seconds"] = time.perf_counter() - t
            print(json.dumps(row), flush=True)
            del session, out
            gc.collect()
    except harness.Refused as e:
        log(f"probe: {e}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
