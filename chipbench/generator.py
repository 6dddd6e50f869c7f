"""The one traffic generator.  A traffic mix is a data file under
``chipbench/traffic/`` that this module reads; nothing in it is code.

Keys of a mix:

``loop``         ``"closed"``: ``clients`` callers, each sending its
                 next request when the previous answer came back;
                 ``"open"``: one submitter sends on a seeded schedule
                 (``arrivals`` ``"poisson"`` at ``rate_per_s``) whatever
                 the answers do, and a refused request is failed.
``clients``      closed loop: the number of callers.
``rate_per_s``   open loop: the offered rate.
``input_pool``   distinct requests drawn from the seed; request ``i``
                 sends pool entry ``order[i mod pool]``, ``order`` a
                 seeded permutation, so every seed sends the same sizes
                 in another order.
``sample``       answers kept for the check: a seeded reservoir over the
                 requests sent in the window.
``warmup_s``     the same traffic before the window (set-up).
``gateway``      ``AsyncServeConfig`` fields of the gateway.
``trace``        ``start_s``/``length_s`` of the profiled span, from the
                 window's start, in a traced run.

Latency runs from when a request was due (closed loop: when its caller
sent it; open loop: its scheduled time) to when its answer reached the
caller.  The window holds the requests due in ``[t0, t0 + seconds)``;
throughput counts the answers that arrived in it.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

ANSWER_WAIT_S = 60.0      # how long after the window an answer may come


@dataclass
class Sampler:
    """Seeded reservoir of ``size`` answers over the window's requests
    in the order they were sent."""
    size: int
    rng: np.random.Generator
    seen: int = 0
    kept: dict = field(default_factory=dict)   # slot → (pool index, answer)

    def offer(self) -> Optional[int]:
        """Register one request of the window; the slot it takes in the
        reservoir, or None when it is not kept."""
        self.seen += 1
        if self.seen <= self.size:
            return self.seen - 1
        j = int(self.rng.integers(0, self.seen))
        return j if j < self.size else None


@dataclass
class Outcome:
    due: np.ndarray           # due times of the window's requests
    done: np.ndarray          # answer times (inf: failed or never came)
    answers_in_window: int    # answers that arrived inside the window
    sampled: List[tuple]      # (pool index, answer) of the kept answers
    lag_s: np.ndarray         # open loop: how late each send ran
    shed: int                 # open loop: refused at admission
    t0: float                 # host clock at the window's start
    t1: float                 # host clock at the window's end
    trace_span: Optional[tuple] = None   # host clock (start, stop)


class Traffic:
    def __init__(self, mix: dict, seed: int, pool: list):
        self.mix = mix
        self.pool = pool
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
        self.order = rng.permutation(len(pool))
        self.sampler = Sampler(mix["sample"], np.random.default_rng(
            [seed & 0xFFFFFFFF, seed >> 32, 11]))
        self.arrival_rng = np.random.default_rng(
            [seed & 0xFFFFFFFF, seed >> 32, 13])

    def run(self, gateway, plan_id: str, seconds: float, *,
            annotate: Callable = None, trace: Callable = None
            ) -> Outcome:
        """Drive ``gateway`` for ``warmup_s`` then the ``seconds`` window.
        ``annotate(name)`` gives a context manager for the benchmark's
        own spans; ``trace(start)`` starts (True) or stops (False) the
        profiler, off the event loop, and returns the host clock inside
        the profiled span."""
        return asyncio.run(self._run(gateway, plan_id, seconds,
                                     annotate, trace))

    async def _run(self, gw, plan_id, seconds, annotate, trace):
        import contextlib
        annotate = annotate or (lambda name: contextlib.nullcontext())
        mix = self.mix
        t0 = time.perf_counter() + mix["warmup_s"]
        t1 = t0 + seconds
        due, done, lag = [], [], []
        answered_in, shed = [0], [0]
        pool, order, sampler = self.pool, self.order, self.sampler

        def record(seq_due, idx, slot, out, ok):
            now = time.perf_counter()
            if t0 <= now < t1:
                answered_in[0] += 1
            if seq_due < t0:
                return
            due.append(seq_due)
            done.append(now if ok else np.inf)
            if slot is not None and ok:
                sampler.kept[slot] = (idx, np.array(out))

        async def caller(c: int):
            n, clients = 0, mix["clients"]
            while True:
                sent = time.perf_counter()
                if sent >= t1:
                    return
                idx = int(order[(c + n * clients) % len(order)])
                n += 1
                slot = sampler.offer() if sent >= t0 else None
                with annotate("chipbench.client.submit"):
                    fut = await gw.submit(pool[idx], plan_id=plan_id)
                try:
                    out = await asyncio.wait_for(fut, ANSWER_WAIT_S)
                    ok = True
                except Exception:        # noqa: BLE001 — a failed answer
                    out, ok = None, False
                with annotate("chipbench.client.record"):
                    record(sent, idx, slot, out, ok)

        async def submitter():
            from repro.serve import GatewayBacklog
            rate = mix["rate_per_s"]
            futs, n, t = [], 0, time.perf_counter()
            while True:
                t += self.arrival_rng.exponential(1.0 / rate)
                if t >= t1:
                    break
                delay = t - time.perf_counter()
                await asyncio.sleep(max(delay, 0.0))
                idx = int(order[n % len(order)])
                n += 1
                slot = sampler.offer() if t >= t0 else None
                if t >= t0:
                    lag.append(time.perf_counter() - t)
                with annotate("chipbench.client.submit"):
                    try:
                        fut = gw.submit_nowait(pool[idx], plan_id=plan_id)
                    except GatewayBacklog:
                        if t >= t0:
                            shed[0] += 1
                        record(t, idx, None, None, False)
                        continue
                fut.add_done_callback(
                    lambda f, t=t, idx=idx, slot=slot: record(
                        t, idx, slot,
                        None if f.cancelled() or f.exception() else
                        f.result(),
                        not f.cancelled() and f.exception() is None))
                futs.append(fut)
            if futs:
                await asyncio.wait(futs, timeout=ANSWER_WAIT_S)
            for f in futs:        # an answer that never came has failed
                f.cancel()

        async def tracer():
            spec = mix["trace"]
            loop = asyncio.get_running_loop()
            await asyncio.sleep(max(0.0, t0 + spec["start_s"]
                                    - time.perf_counter()))
            start = await loop.run_in_executor(None, trace, True)
            await asyncio.sleep(spec["length_s"])
            stop = await loop.run_in_executor(None, trace, False)
            return start, stop

        async with gw:
            tasks = ([asyncio.create_task(caller(c))
                      for c in range(mix["clients"])]
                     if mix["loop"] == "closed"
                     else [asyncio.create_task(submitter())])
            tracing = asyncio.create_task(tracer()) if trace else None
            await asyncio.gather(*tasks)
            span = await tracing if tracing else None

        sampled = [sampler.kept[k] for k in sorted(sampler.kept)]
        return Outcome(due=np.asarray(due), done=np.asarray(done),
                       answers_in_window=answered_in[0],
                       sampled=sampled, lag_s=np.asarray(lag),
                       shed=shed[0], t0=t0, t1=t1, trace_span=span)
