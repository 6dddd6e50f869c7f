"""The family of one chip's share of DeepSeek-V3's MoE layers
(``families/moe_ep.py``) at a size a test can hold, on the CPU: a whole
run reads correct, the control and each planted fault read not correct
by the committed configuration's own limits, the work is counted from
shapes, a program that cannot hold a share is refused at set-up, and
the three readers of the cell read what they are defined as."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness, work
from chipbench.tests.test_check import (_cell, _run,  # noqa: F401
                                        isolated_jax)

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ROOT / "chipbench" / "configs"
CONFIG = json.loads((CONFIGS / "deepseek-v3-moe-ep32-8L.json").read_text())
LIMITS = CONFIG["check"]["limits"]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())

# d 64, 32 experts in 4 groups, top-4 from the best 2 groups, 8 held;
# the cell's 8 layers, over which the control's rounding compounds
TINY = dict(hidden_size=64, router_experts=32, n_routed_experts=8,
            expert_offset=0, n_group=4, topk_group=2, num_experts_per_tok=4,
            moe_intermediate_size=32, seq_len=16, num_hidden_layers=8)


def _bits():
    plan = json.loads((CONFIGS / CONFIG["plan_file"].split("/")[-1])
                      .read_text())
    return [(a["data_bits"], a["coeff_bits"]) for a in plan["layers"]]


def _config(tmp_path, **over):
    from repro.runtime import MoEWorkloadSpec, plan_moe_deployment, save_plan
    from repro.runtime.workloads import MoELayerSpec
    cfg = {**CONFIG, **TINY, **over}
    spec = MoEWorkloadSpec(layers=tuple(
        MoELayerSpec(d_ff_expert=cfg["moe_intermediate_size"],
                     num_experts=cfg["router_experts"],
                     top_k=cfg["num_experts_per_tok"], data_bits=d,
                     coeff_bits=c,
                     n_shared_experts=cfg["n_shared_experts"],
                     capacity_factor=cfg["capacity_factor"],
                     scoring=cfg["scoring_func"], n_group=cfg["n_group"],
                     topk_group=cfg["topk_group"],
                     routed_scaling_factor=cfg["routed_scaling_factor"],
                     experts_held=cfg["n_routed_experts"],
                     expert_offset=cfg["expert_offset"])
        for d, c in _bits()[:cfg["num_hidden_layers"]]),
        d_model=cfg["hidden_size"],
        seq_len=cfg["seq_len"])
    path = tmp_path / "moe_ep.plan.json"
    save_plan(plan_moe_deployment(spec, "v5e", bit_candidates=None,
                                  on_infeasible="fallback"), path)
    cfg["plan_file"] = str(path)
    return cfg


@pytest.fixture
def cell(tmp_path):
    return _cell("moe_ep", _config(tmp_path))


def test_program_reads_correct_and_counts_its_share(cell):
    r = _run(cell)
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["check"]) == set(LIMITS)


def test_control_reads_not_correct(cell):
    r = _run(cell, control=True)
    assert not r["correct"], r["check"]


@pytest.fixture(scope="module")
def fault_numbers(tmp_path_factory):
    """``compare``'s numbers of each fault ``Model.faults`` plants, against
    the reference, on one model and 12 blocks."""
    harness.import_program(ROOT)
    cfg = _config(tmp_path_factory.mktemp("faults"))
    model = _cell("moe_ep", cfg).family.Model(cfg, 2**33 + 9, ROOT)
    xs = np.stack(model.inputs(12))
    want = model.reference(xs)
    return {name: model.compare(got, want, xs)
            for name, got in model.faults(xs).items()}


@pytest.mark.parametrize("fault", ["bias_left_out", "no_group_limit",
                                   "no_scaling", "no_shared_expert",
                                   "capacity_capped"])
def test_planted_fault_fails_a_limit(fault_numbers, fault):
    numbers = fault_numbers[fault]
    assert any(numbers[k] > v for k, v in LIMITS.items()), numbers


def test_reference_agrees_with_the_program_layer_by_layer(tmp_path):
    """The family's reference against the program's eager stack on the
    same weights (the one comparison a run makes, without the gateway)."""
    from repro.runtime.workloads import _eager_forward
    model = _cell("moe_ep", _config(tmp_path)).family.Model(
        _config(tmp_path), 7, ROOT)
    xs = np.stack(model.inputs(3))
    got = np.asarray(_eager_forward(model.spec, model.params, xs))
    numbers = model.compare(got, model.reference(xs), xs)
    assert all(numbers[k] <= v for k, v in LIMITS.items()), numbers


def test_dispatch_work_counts():
    """Per token: the router over 256 experts, 8/256 of the top-8
    routed FFNs, the shared FFN; bytes: 8 held and 1 shared expert at
    4 bits, the router at 32, 4-bit tokens in and out."""
    from chipbench.families import moe_ep
    d, f, tokens = 7168, 2048, 16 * 256
    w = moe_ep.layer_work(tokens, d, 256, 8, 8, f, f, 4, 4)
    per_token = 2 * d * 256 + 8 * 8 / 256 * 6 * d * f + 6 * d * f
    assert w.ops == pytest.approx(tokens * per_token)
    assert w.bytes == pytest.approx(9 * 3 * d * f / 2 + d * 256 * 4
                                    + 2 * tokens * d / 2)
    model = SimpleNamespace(s=256, d=d, e=256, held=8, k=8, f=f, fs=f,
                            bits=[(4, 4)] * 8)
    assert moe_ep.Model.dispatch_work(model, 16) == [w] * 8
    # a layer that held every expert and no shared one is moe_layer's
    assert moe_ep.layer_work(tokens, d, 256, 256, 8, f, 0, 4, 4) == (
        work.moe_layer(tokens, d, 256, 8, f, 4, 4))


def test_a_program_without_held_experts_is_refused(tmp_path, monkeypatch):
    """The parent program's ``MoELayerSpec`` has no ``experts_held``:
    set-up stops at once, before the plan (whose layers such a program
    cannot read) or any weight."""
    import dataclasses

    import repro.runtime
    from repro.runtime import workloads

    @dataclasses.dataclass(frozen=True)
    class ParentLayerSpec:
        d_ff_expert: int
        num_experts: int
        top_k: int
        capacity_factor: float = 2.0

    def no_plan(path):
        raise AssertionError("the plan was read")

    cfg = _config(tmp_path)
    fam = _cell("moe_ep", cfg).family
    monkeypatch.setattr(workloads, "MoELayerSpec", ParentLayerSpec)
    monkeypatch.setattr(repro.runtime, "load_plan", no_plan)
    with pytest.raises(harness.Refused, match="experts_held"):
        fam.Model(cfg, 1, ROOT)


def _counts(routed, kept, hits):
    return {"kind": "moe", "bucket_hits": hits, "calls": 0,
            "moe_routed_held": routed, "moe_kept_held": kept}


def _ctx(start, stop):
    return SimpleNamespace(marks={"start": {"stats": {"p": start}},
                                  "stop": {"stats": {"p": stop}}},
                           model=SimpleNamespace(held=8),
                           compiled=SimpleNamespace(num_layers=8))


@pytest.mark.parametrize("name, want", [
    # 3 dispatches of 8 layers, 8 held experts: 2880 kept
    ("moe_ep.held_load", 2880 / (8 * 8 * 3)),
    ("moe_ep.drop_share", 100.0 * (1 - 2880 / 3200)),
])
def test_counter_reader_value(name, want):
    reader = harness.load_module(ROOT / "chipbench" / "metrics"
                                 / f"{name}.py", f"chipbench_metric_{name}")
    ctx = _ctx(_counts(100, 90, {16: 1}), _counts(3300, 2970, {16: 4}))
    assert reader.read(ctx) == pytest.approx(want)
    # the parent program keeps no such counters: nothing to read
    old = {"kind": "moe", "bucket_hits": {16: 4}, "calls": 4}
    assert reader.read(_ctx(old, old)) is None
    # a span in which nothing ran
    assert reader.read(_ctx(_counts(100, 90, {16: 1}),
                            _counts(100, 90, {16: 1}))) is None


def test_roofline_reader_is_kernels_roofline_over_this_family():
    from chipbench.families import moe_ep
    name = "moe_ep.roofline"
    reader = harness.load_module(ROOT / "chipbench" / "metrics"
                                 / f"{name}.py", f"chipbench_metric_{name}")
    base = harness.load_module(ROOT / "chipbench" / "metrics"
                               / "kernels_roofline.py", "kr")
    model = SimpleNamespace(s=256, d=7168, e=256, held=8, k=8, f=2048,
                            fs=2048, bits=[(4, 4)] * 8)
    ctx = SimpleNamespace(
        summary=SimpleNamespace(module_s={"jit_moe_x": 0.5, "other": 9.0}),
        layer_modules=frozenset({"jit_moe_x"}), dispatches={16: 20, 3: 1},
        device_kind="TPU v5 lite", ops_bits=8,
        dispatch_work=lambda n: moe_ep.Model.dispatch_work(model, n))
    assert reader.read(ctx) == pytest.approx(base.read(ctx))
    assert 0 < reader.read(ctx) < 100


def test_new_metrics_list_the_new_cell_alone():
    new = {m["name"]: m for m in MANIFEST["per_layer"]
           if m["name"].startswith("moe_ep.")}
    assert set(new) == {"moe_ep.roofline", "moe_ep.held_load",
                        "moe_ep.drop_share"}
    for m in new.values():
        assert m["workloads"] == ["dsv3moe.closed32"]
        assert m["moves"] == "throughput"
