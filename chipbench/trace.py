"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the traced window, device busy time, device time per
executable and per operation, the runtime's host↔device transfer time,
and the device's longest idle gaps with what the host was doing in each.

Read with ``jax.profiler.ProfileData``: planes, lines and events with
a start and a duration in nanoseconds.  On a TPU v5e trace a device
plane is named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per executed operation, named by the HLO instruction's text, and
its ``XLA Modules`` line one per executable run, named
``<module>(<program id>)``.  Host↔device copies leave no event on the
device plane; the runtime's host-side transfer spans (layout conversion
and the DMA dispatch) are on ``/host:CPU`` with the threads' other
TraceMe spans, the benchmark's own ``chipbench.*`` annotations among
them.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the runtime's host-side work of one host↔device copy
TRANSFER_SPANS = frozenset({"XlaLinearize", "XlaDelinearize",
                            "H2D Dispatch", "D2H Dispatch"})
BENCH_SPAN = "chipbench."
UNANNOTATED = "gateway (unannotated)"
TOP = 10


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _module_name(name: str) -> str:
    """``jit_layer(1234)`` → ``jit_layer``: the executable's name
    without the program id."""
    return re.sub(r"\(-?\d+\)$", "", name)


def _op_label(name: str) -> str:
    """``%fusion.25 = f32[16,128]{1,0:T(8,128)} fusion(...), ...`` →
    ``fusion.25 f32[16,128] fusion``: the instruction, its result type
    without layout, and its opcode."""
    m = re.match(r"%?([\w.\-]+) = (\S+?)(?:\{[^ ]*\})? ([\w\-]+)\(", name)
    return " ".join(m.groups()) if m else name


@dataclass
class Summary:
    window_s: float                       # length of the traced window
    busy_s: float                         # device busy, mean over chips
    chips: int
    op_s: Dict[str, float] = field(default_factory=dict)   # mean/chip
    module_s: Dict[str, float] = field(default_factory=dict)  # mean/chip
    module_runs: Dict[str, int] = field(default_factory=dict)  # mean/chip
    transfer_s: float = 0.0               # host spans of copies (union)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce(pd) -> Summary:
    """The ``Summary`` of one ``ProfileData``."""
    lo, hi = None, None
    devices, host_spans = [], []
    for plane in pd.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        if not (is_device or plane.name.startswith("/host:")):
            continue
        lines = {}
        for line in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in line.events]
            for _, s, d in events:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
            lines[line.name] = events
            if not is_device:
                host_spans.extend((n, s, s + d) for n, s, d in events
                                  if d > 0)
        if is_device:
            devices.append(lines)
    if lo is None or not devices:
        return Summary(0.0, 0.0, len(devices))
    n = len(devices)
    busy_ns = 0
    op_s: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    module_runs: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[int, int]] = []
    for lines in devices:
        ops = lines.get(OPS_LINE, [])
        modules = lines.get(MODULES_LINE, [])
        busy = _union([(s, s + d) for _, s, d in (ops or modules)])
        busy_ns += sum(e - s for s, e in busy)
        for name, _, d in ops:
            op_s[_op_label(name)] += d / 1e9 / n
        for name, _, d in modules:
            module_s[_module_name(name)] += d / 1e9 / n
            module_runs[_module_name(name)] += 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend((s, e) for s, e in zip(edges[::2], edges[1::2])
                    if e > s)
    transfer = _union([(s, e) for name, s, e in host_spans
                       if name in TRANSFER_SPANS])
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9 / n,
                   chips=n, op_s=dict(op_s), module_s=dict(module_s),
                   module_runs={k: v // n for k, v in module_runs.items()},
                   transfer_s=sum(e - s for s, e in transfer) / 1e9,
                   gaps=[(_attribute(s, e, host_spans), (e - s) / 1e9)
                         for s, e in longest])


def _attribute(s: int, e: int, host_spans) -> str:
    """What the host was doing in the device's idle gap ``[s, e)``: the
    benchmark's own span name whose spans cover the most of it, if they
    cover half; else the host span name that does; else
    ``UNANNOTATED``."""
    cover: Dict[str, int] = defaultdict(int)
    for name, hs, he in host_spans:
        ov = min(e, he) - max(s, hs)
        if ov > 0:
            cover[name] += ov
    for own in (True, False):
        named = [(ov, name) for name, ov in cover.items()
                 if name.startswith(BENCH_SPAN) == own]
        if named:
            ov, name = max(named)
            if 2 * ov >= e - s:
                return name if own else f"host: {name}"
    return UNANNOTATED


def reduce_file(path: str) -> Summary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(path)))


def find_xplane(logdir) -> str:
    found = sorted(glob.glob(os.path.join(str(logdir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def reduce_dir(logdir, chips: int) -> Summary:
    summary = reduce_file(find_xplane(logdir))
    if summary.chips != chips:
        raise ValueError(f"the trace holds {summary.chips} device planes, "
                         f"the run used {chips} chips")
    return summary
