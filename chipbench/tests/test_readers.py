"""The five readers of the program's own spans and counters: on
synthetic captures at the ends of the profiled span, the value each
is defined as, and nothing on captures of a program that keeps no
spans."""

import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import harness
from repro.serve.slots import GatewayStats

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ("gateway.submit_ms", "gateway.queue_wait_ms", "gateway.loop_share",
       "executor.host_ms", "executor.padded_share")


def _reader(name):
    return harness.load_module(ROOT / "chipbench" / "metrics" / f"{name}.py",
                               f"chipbench_metric_{name}")


def _gateway(spans, launched, queue_wait_s):
    return GatewayStats(timestamp=0.0, queue_depth=0, inflight=0,
                        max_batch=16, steps=0, launched=launched,
                        queue_wait_s=queue_wait_s, spans=spans)


def _executor(spans, rows, padded_rows, hits):
    return {"kind": "cnn", "bucket_hits": hits, "calls": 0, "rows": rows,
            "padded_rows": padded_rows, "spans": spans}


def _ctx():
    start = {"gateway": _gateway({"gateway.submit": (10, 0.01),
                                  "gateway.handoff": (2, 0.002)}, 20, 1.0),
             "stats": {"p": _executor({"executor.h2d": (2, 0.004)}, 32, 2,
                                      {8: 1, 16: 1})}}
    stop = {"gateway": _gateway(
        {"gateway.submit": (42, 0.074), "gateway.form_batch": (2, 0.001),
         "gateway.stack": (2, 0.02), "gateway.resolve": (2, 0.005),
         "gateway.handoff": (4, 0.006), "executor.device_wait": (2, 0.5),
         "executor.d2h": (2, 0.03)}, 52, 9.0),
        "stats": {"p": _executor(
            {"executor.h2d": (4, 0.012), "executor.pad": (1, 0.001),
             "executor.launch": (4, 0.002)}, 64, 6, {8: 1, 16: 3})}}
    return SimpleNamespace(marks={"start": start, "stop": stop}, span_s=2.0)


@pytest.mark.parametrize("name, want", [
    ("gateway.submit_ms", 1e3 * 0.064 / 32),
    ("gateway.queue_wait_ms", 1e3 * 8.0 / 32),
    ("gateway.loop_share", 100.0 * (0.064 + 0.001 + 0.02 + 0.005) / 2.0),
    # handoff 0.004 + pad 0.001 + h2d 0.008 + launch 0.002 + d2h 0.03,
    # over two bucket runs; the device wait is left out
    ("executor.host_ms", 1e3 * 0.045 / 2),
    ("executor.padded_share", 100.0 * 4 / 32),
])
def test_reader_value(name, want):
    assert _reader(name).read(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_spans(name):
    """The parent program's captures: a ``GatewayStats`` without the
    span fields and executor stats without ``spans``/``rows``."""
    @dataclasses.dataclass
    class OldStats:
        served: int = 0

    old = {"gateway": OldStats(),
           "stats": {"p": {"kind": "cnn", "bucket_hits": {16: 3},
                           "calls": 3}}}
    ctx = SimpleNamespace(marks={"start": old, "stop": old}, span_s=2.0)
    assert _reader(name).read(ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_span_without_work(name):
    ctx = _ctx()
    ctx.marks["stop"] = ctx.marks["start"]
    ctx.span_s = 0.0
    assert _reader(name).read(ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_is_in_the_manifest_for_every_cell(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(entry["workloads"]) <= cells
    assert {"vgg16s2.closed32", "qwen3moe.closed32"} <= set(
        entry["workloads"])


def test_layer_modules_finds_the_named_executables():
    """``harness._layer_modules`` reads each layer executable's name from
    its compiled text; the names are the layers' own."""
    from repro.core import deploy
    from repro.core.cnn import CNNConfig, ConvLayerSpec, fitted_block_models
    from repro.runtime.workloads import compile_plan

    cfg = CNNConfig(layers=(
        ConvLayerSpec(1, 2, data_bits=8, coeff_bits=6, block="conv4"),
        ConvLayerSpec(2, 2, data_bits=6, coeff_bits=4, block="conv3"),
    ), img_h=16, img_w=64)
    plan = deploy.plan_deployment(cfg, fitted_block_models(), target=0.8,
                                  on_infeasible="fallback")
    compiled = compile_plan(plan, max_batch=2)
    assert harness._layer_modules(compiled) == {
        f"jit_cnn_{b}_d{s.data_bits}c{s.coeff_bits}_"
        f"{s.in_channels}to{s.out_channels}"
        for s, b in zip(compiled.cfg.layers, plan.block_names())}
