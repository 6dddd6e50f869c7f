#!/usr/bin/env python3
"""Time the gateway's per-request numpy work in a serving process, once
with the gateway idle and once from a task on the event loop while a
cell's closed loop serves, to tell apart what makes it slow there:
writing fresh memory, reading the callers' maps, or the whole loop.

    python3 scripts/host_probe.py --workload vgg16s2.closed32 \\
        --seed 3700000001 [--reps 30] [--seconds 8]

Calls, over the cell's input pool (``n`` = the gateway's ``max_batch``):

* ``a_validate_pool``   ``validate_input`` of a pool map;
* ``b_validate_copy``   the same of a private copy (made untimed);
* ``c_stack_new``       ``np.stack`` of ``n`` pool maps into a new array;
* ``d_stack_into``      the same into one buffer written once before;
* ``e_stack_private``   (d) over ``n`` private copies;
* ``f_copy_map``        ``pool[i].copy()``: one map into fresh memory;
* ``g_python``          a pure-Python loop of about the same idle cost;
* ``h_put_pool``        ``jax.device_put`` of ``n`` pool maps, waited for;
* ``i_put_private``     (h) over the ``n`` private copies.

Each call reports the median wall time and thread CPU time
(``time.thread_time``), and the mean user and system time and minor
page faults of the calling thread (``getrusage(RUSAGE_THREAD)``, whose
times may tick coarsely).  Wall far above CPU: the thread waited
(lock, scheduler); system time with faults: fresh pages.  Prints one
JSON object per phase (``system`` with the pool maps' layout, ``idle``,
``serving``, ``idle_after``) and the gateway's span totals of the
serving phase; needs a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)


def _calls(compiled, pool, n):
    import jax
    validate = compiled.validate_input
    private = [p.copy() for p in pool[:n]]
    buf = np.stack(pool[:n])              # written once, then reused
    k = len(pool)

    def window(i):
        return [pool[(i + j) % k] for j in range(n)]

    def python(i):
        s = 0
        for j in range(3000):
            s += j ^ i
        return s

    return {
        "a_validate_pool": lambda i: validate(pool[i % k]),
        "b_validate_copy": lambda i: validate(private[i % n]),
        "c_stack_new": lambda i: np.stack(window(i)),
        "d_stack_into": lambda i: np.stack(window(i), out=buf),
        "e_stack_private": lambda i: np.stack(private, out=buf),
        "f_copy_map": lambda i: pool[i % k].copy(),
        "g_python": python,
        "h_put_pool": lambda i: jax.block_until_ready(
            jax.device_put(window(i))),
        "i_put_private": lambda i: jax.block_until_ready(
            jax.device_put(private)),
    }


def _timed(fn, i):
    r0 = resource.getrusage(resource.RUSAGE_THREAD)
    c0, t0 = time.thread_time(), time.perf_counter()
    fn(i)
    t1, c1 = time.perf_counter(), time.thread_time()
    r1 = resource.getrusage(resource.RUSAGE_THREAD)
    return (1e3 * (t1 - t0), 1e3 * (c1 - c0),
            1e3 * (r1.ru_utime - r0.ru_utime),
            1e3 * (r1.ru_stime - r0.ru_stime), r1.ru_minflt - r0.ru_minflt)


def _summary(samples):
    out = {}
    for name, rows in samples.items():
        cols = list(zip(*rows))
        out[name] = {"n": len(rows),
                     "wall_ms": statistics.median(cols[0]),
                     "cpu_ms": statistics.median(cols[1]),
                     "user_ms_mean": statistics.fmean(cols[2]),
                     "sys_ms_mean": statistics.fmean(cols[3]),
                     "minflt_mean": statistics.fmean(cols[4]),
                     "wall_ms_max": max(cols[0])}
    return out


def _idle(calls, reps):
    samples = {name: [] for name in calls}
    for i in range(reps):
        for name, fn in calls.items():
            samples[name].append(_timed(fn, i))
    return _summary(samples)


async def _serving(calls, reps, session, seconds, mix):
    from chipbench import generator
    traffic = generator.Traffic(mix, session.seed, session.pool)
    run = asyncio.create_task(traffic._run(session.gateway, session.plan_id,
                                           seconds, None, None))
    samples = {name: [] for name in calls}
    await asyncio.sleep(mix["warmup_s"] + 1.0)
    spans0 = session.gateway.snapshot().spans
    for i in range(reps):
        for name, fn in calls.items():
            samples[name].append(_timed(fn, i))
            await asyncio.sleep(0.005)
    spans1 = session.gateway.snapshot().spans
    out = await run
    spans = {k: (c - spans0.get(k, (0, 0.0))[0],
                 1e3 * (s - spans0.get(k, (0, 0.0))[1]))
             for k, (c, s) in spans1.items()}
    return _summary(samples), spans, out


def _layout(a):
    return {"shape": a.shape, "dtype": str(a.dtype), "strides": a.strides,
            "c_contiguous": bool(a.flags.c_contiguous),
            "owndata": bool(a.flags.owndata),
            "writeable": bool(a.flags.writeable),
            "address_mod_4096": a.ctypes.data % 4096,
            "base": type(a.base).__name__}


def _system():
    status = Path("/proc/self/status").read_text()
    threads = next((ln.split()[1] for ln in status.splitlines()
                    if ln.startswith("Threads:")), None)
    thp = {}
    for key in ("enabled", "defrag"):
        p = Path("/sys/kernel/mm/transparent_hugepage") / key
        thp[key] = p.read_text().strip() if p.exists() else None
    return {"cpus": os.cpu_count(),
            "affinity": len(os.sched_getaffinity(0)),
            "threads": threads, "thp": thp,
            "numpy": np.__version__}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="vgg16s2.closed32")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)

    from chipbench import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        cell = harness.resolve(ROOT, args.workload)
        session = harness.Session(ROOT, cell, args.seed, log=log)
    except harness.Refused as e:
        log(f"host_probe: {e}")
        return 2
    calls = _calls(session.compiled, session.pool,
                   cell.traffic["gateway"]["max_batch"])
    print(json.dumps({"phase": "system", **_system(),
                      "pool_map": _layout(session.pool[0]),
                      "pool_base": _layout(session.pool[0].base)
                      if isinstance(session.pool[0].base, np.ndarray)
                      else None}), flush=True)
    print(json.dumps({"phase": "idle",
                      "calls": _idle(calls, args.reps)}), flush=True)
    serving, spans, out = asyncio.run(
        _serving(calls, args.reps, session, args.seconds, cell.traffic))
    print(json.dumps({"phase": "serving", "calls": serving,
                      "answers_in_window": out.answers_in_window,
                      "seconds": args.seconds,
                      "gateway_spans_ms": spans}), flush=True)
    print(json.dumps({"phase": "idle_after",
                      "calls": _idle(calls, args.reps)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
