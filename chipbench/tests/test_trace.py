"""The reduction from a profiler trace to busy/idle, executable and copy
time and the breakdown: on synthetic planes, and on a small trace
recorded on a TPU v5e and committed beside this file."""

from dataclasses import dataclass, field
from pathlib import Path
from typing import List

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data"


@dataclass
class Event:
    name: str
    start_ns: int
    duration_ns: int
    stats: list = field(default_factory=list)


@dataclass
class Line:
    name: str
    events: List[Event]


@dataclass
class Plane:
    name: str
    lines: List[Line]


@dataclass
class Profile:
    planes: List[Plane]


FUSION = ("%fusion.1 = f32[16,128]{1,0:T(8,128)} fusion(f32[16,2048]{1,0} "
          "%p.1), kind=kOutput, calls=%fused_computation")


def _profile():
    ops = [Event(FUSION, 100, 200),
           Event("%copy.3 = s8[4,9]{1,0} copy(s8[4,9]{0,1} %x)", 250, 100),
           Event("%pad.1 = s8[8]{0} pad(s8[4]{0} %y, s8[] %c)", 600, 100)]
    modules = [Event("jit_layer(7)", 100, 250),
               Event("jit_pad(-9)", 600, 100)]
    host = [Event("chipbench.client.submit", 350, 80),
            Event("chipbench.client.submit", 450, 100),
            Event("PjitFunction(layer)", 720, 260),
            Event("XlaLinearize", 700, 50),
            Event("H2D Dispatch", 740, 30),
            Event("short", 0, 10)]
    return Profile([
        Plane("/host:metadata", []),
        Plane("/device:TPU:0", [Line("XLA Ops", ops),
                                Line("XLA Modules", modules)]),
        Plane("/host:CPU", [Line("python", host)]),
    ])


def test_union_merges_overlaps():
    assert trace._union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_reduce_synthetic():
    s = trace.reduce(_profile())
    # the window spans every event of every plane: 0 .. 980 ns
    assert s.window_s == pytest.approx(980e-9)
    # busy: [100, 350) ∪ [600, 700)
    assert s.busy_s == pytest.approx(350e-9)
    assert s.chips == 1
    assert s.module_s == pytest.approx({"jit_layer": 250e-9,
                                        "jit_pad": 100e-9})
    assert s.module_runs == {"jit_layer": 1, "jit_pad": 1}
    # host-side transfer spans, overlaps counted once: [700, 770)
    assert s.transfer_s == pytest.approx(70e-9)
    assert s.op_s == pytest.approx({"fusion.1 f32[16,128] fusion": 200e-9,
                                    "copy.3 s8[4,9] copy": 100e-9,
                                    "pad.1 s8[8] pad": 100e-9})
    gaps = dict((round(sec * 1e9), name) for name, sec in s.gaps)
    # [350, 600): two of the benchmark's spans cover 180 of 250 ns;
    # [700, 980) under a host span; [0, 100) under neither for most
    assert gaps == {250: "chipbench.client.submit",
                    280: "host: PjitFunction(layer)",
                    100: trace.UNANNOTATED}
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.1 f32[16,128] fusion", 200e-9]
    assert [g[1] for g in b["idle_gaps"]] == sorted(
        (g[1] for g in b["idle_gaps"]), reverse=True)


def test_op_label_keeps_name_type_and_opcode():
    assert trace._op_label(FUSION) == "fusion.1 f32[16,128] fusion"
    assert trace._op_label("not an instruction") == "not an instruction"


def test_no_device_plane_reads_nothing():
    p = _profile()
    p.planes = [pl for pl in p.planes if not pl.name.startswith("/device")]
    s = trace.reduce(p)
    assert s.chips == 0 and s.busy_s == 0.0


def test_recorded_v5e_trace():
    """50 ms of ``qwen3moe.closed32`` on one TPU v5e: six runs of the
    layer executable (about three dispatches of 16 token blocks, cut at
    the window's edges) and the host's transfers between them."""
    s = trace.reduce_file(DATA / "qwen3moe.v5e.xplane.pb")
    assert s.chips == 1
    assert s.window_s == pytest.approx(0.050362347)
    assert s.busy_s == pytest.approx(0.037644214)
    assert s.module_runs == {"jit_layer": 6}
    assert s.module_s["jit_layer"] == pytest.approx(0.037646574)
    assert 0 < s.transfer_s < s.window_s - s.busy_s
    b = s.breakdown()
    assert len(b["device_ops"]) == trace.TOP
    assert b["device_ops"][0][0] == "fusion.25 f32[16,128,8,768] fusion"
    assert 0 < len(b["idle_gaps"]) <= trace.TOP
    assert sum(g for _, g in b["idle_gaps"]) <= s.window_s - s.busy_s
