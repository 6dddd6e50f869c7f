#!/usr/bin/env python3
"""Offer open-loop Poisson load at several fixed rates to one
configuration, to find the highest rate it sustains without a growing
backlog (the knee an open-loop cell is set below).

    python3 chipbench/sweep.py --config <name> --traffic <mix> \\
        --rates 500,1000,1500 --seconds 5 --seed <n>

One process sets the configuration up once, then runs one window per
rate through a fresh gateway over the same executables, with the mix's
gateway settings, input pool and warm-up.  Each rate prints one JSON
line: offered and answered rates, p50/p95 latency from the scheduled
send, p95 of the window's first and second half (a growing backlog
shows as a second half slower than the first), how late the generator
ran, and the requests refused.  Needs a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="closed32")
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import numpy as np
    from chipbench import generator, harness

    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == args.config)
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((ROOT / "chipbench" / "traffic"
                      / f"{args.traffic}.json").read_text())
    family = harness.load_module(
        ROOT / "chipbench" / "families" / f"{config['family']}.py",
        f"chipbench.families.{config['family']}")
    try:
        harness.check_devices(1)
        harness.import_program(ROOT)
    except harness.Refused as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    from repro.serve import AsyncCNNGateway, AsyncServeConfig
    harness.compile_cache(ROOT)

    model = family.Model(config, args.seed, ROOT)
    first = AsyncCNNGateway(AsyncServeConfig(**mix["gateway"]))
    plan_id = model.register(first)
    compiled = first.plans[plan_id].compiled
    pool = model.inputs(mix["input_pool"])
    print(f"[sweep] {args.config}: {model.describe()}; set-up "
          f"{time.perf_counter() - T_START:.1f}s", file=sys.stderr)
    for rate in (float(r) for r in args.rates.split(",")):
        open_mix = dict(mix, loop="open", arrivals="poisson",
                        rate_per_s=rate)
        gw = AsyncCNNGateway(AsyncServeConfig(**mix["gateway"]),
                             exec_cache=first.exec_cache)
        gw.register_plan(model.plan, plan_id=plan_id, compiled=compiled)
        out = generator.Traffic(open_mix, args.seed, pool).run(
            gw, plan_id, args.seconds)
        ok = np.isfinite(out.done)
        lat = np.where(ok, out.done - out.due, np.inf)
        half = out.due < out.t0 + args.seconds / 2
        row = {"rate_per_s": rate,
               "answered_per_s": out.answers_in_window / args.seconds,
               "requests": int(len(out.due)), "failed": int((~ok).sum()),
               "shed": out.shed,
               "p50_ms": 1e3 * harness.nearest_rank(lat, 0.5),
               "p95_ms": 1e3 * harness.nearest_rank(lat, 0.95),
               "p95_first_half_ms":
                   1e3 * harness.nearest_rank(lat[half], 0.95),
               "p95_second_half_ms":
                   1e3 * harness.nearest_rank(lat[~half], 0.95),
               "lag_p95_ms": 1e3 * harness.nearest_rank(out.lag_s, 0.95)}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
