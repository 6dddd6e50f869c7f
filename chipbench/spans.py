"""The program's own span totals and counters, as they advanced over the
profiled span: the difference between the two captures the harness
takes at its ends (``ctx.marks["start"]``/``["stop"]``: the gateway's
``GatewayStats`` and each plan's ``CompiledModel.stats()``).  Span
totals are ``{name: (count, seconds)}``.  A program that keeps no such
totals gives None, and the readers built on these report nothing."""

from typing import Dict, Optional, Tuple

Spans = Dict[str, Tuple[int, float]]


def _advance(a: Spans, b: Spans) -> Spans:
    out = {}
    for name, (count, seconds) in b.items():
        c0, s0 = a.get(name, (0, 0.0))
        out[name] = (count - c0, seconds - s0)
    return out


def _merge(into: Spans, more: Spans) -> None:
    for name, (count, seconds) in more.items():
        c0, s0 = into.get(name, (0, 0.0))
        into[name] = (c0 + count, s0 + seconds)


def gateway(ctx) -> Optional[dict]:
    """``spans`` (``gateway.*``, and the dispatch worker's
    ``executor.device_wait``/``executor.d2h``), ``launched`` and
    ``queue_wait_s`` of the gateway, advanced over the span."""
    g0, g1 = ctx.marks["start"]["gateway"], ctx.marks["stop"]["gateway"]
    if not (hasattr(g0, "spans") and hasattr(g1, "spans")):
        return None
    return {"spans": _advance(g0.spans, g1.spans),
            "launched": g1.launched - g0.launched,
            "queue_wait_s": g1.queue_wait_s - g0.queue_wait_s}


def executor(ctx) -> Optional[dict]:
    """``spans`` (``executor.*``), ``rows``, ``padded_rows`` and
    ``dispatches`` (bucket runs) of the executors, summed over the
    plans and advanced over the span."""
    s0, s1 = ctx.marks["start"]["stats"], ctx.marks["stop"]["stats"]
    total = {"spans": {}, "rows": 0, "padded_rows": 0, "dispatches": 0}
    for pid, b in s1.items():
        a = s0.get(pid)
        if a is None or "spans" not in a or "spans" not in b:
            return None
        _merge(total["spans"], _advance(a["spans"], b["spans"]))
        total["rows"] += b["rows"] - a["rows"]
        total["padded_rows"] += b["padded_rows"] - a["padded_rows"]
        total["dispatches"] += (sum(b["bucket_hits"].values())
                                - sum(a["bucket_hits"].values()))
    return total if s1 else None


def seconds(spans: Spans, *names: str) -> float:
    """The summed seconds of ``names`` in ``spans``."""
    return sum(spans.get(n, (0, 0.0))[1] for n in names)
