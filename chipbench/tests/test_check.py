"""The check that decides ``correct``, driven through a whole run on the
CPU at a size a test can hold (the harness's look for a chip skipped):
the program reads correct, and the control and a broken timed path read
not correct.  The limits are the committed configurations' own."""

import json
from pathlib import Path

import jax
import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ROOT / "chipbench" / "configs"
_JAX_OPTIONS = ("jax_default_matmul_precision",)


@pytest.fixture(autouse=True)
def isolated_jax(monkeypatch):
    """A run here neither turns on JAX's compilation cache nor leaves
    its process-wide options changed for later tests."""
    harness.import_program(ROOT)
    monkeypatch.setattr(harness, "compile_cache", lambda root: None)
    saved = {k: getattr(jax.config, k) for k in _JAX_OPTIONS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def _traffic():
    mix = json.loads((ROOT / "chipbench" / "traffic" / "closed32.json")
                     .read_text())
    mix.update(clients=4, input_pool=12, sample=12, warmup_s=0.2)
    mix["gateway"]["max_batch"] = 4
    return mix


def _cnn_config():
    cfg = json.loads((CONFIGS / "vgg16-s2-int8.json").read_text())
    cfg.update(img_h=16, img_w=16)
    cfg["layers"][0].update(in_channels=8, out_channels=16)
    cfg["layers"][1].update(in_channels=16, out_channels=16)
    return cfg


@pytest.fixture(autouse=True)
def pinned_cnn_blocks(monkeypatch):
    """The planner's fitted models need the synthesis sweep; here the
    plan pins blocks that take the layer-fused dot paths the chip's plan
    takes, and the planner only checks them."""
    import dataclasses
    from repro.core import allocate, cnn, deploy
    plan_deployment = deploy.plan_deployment

    def pinned(cfg, models, device, **kw):
        cfg = dataclasses.replace(cfg, layers=tuple(
            dataclasses.replace(s, block=b)
            for s, b in zip(cfg.layers, ("conv4", "conv2"))))
        return plan_deployment(cfg, models, device, **kw)

    monkeypatch.setattr(cnn, "fitted_block_models",
                        lambda: allocate.BlockModels(models={}, convs={}))
    monkeypatch.setattr(deploy, "plan_deployment", pinned)


def _moe_config(tmp_path):
    from repro.runtime import MoEWorkloadSpec, plan_moe_deployment, save_plan
    from repro.runtime.workloads import MoELayerSpec
    cfg = json.loads((CONFIGS / "qwen3-moe-30b-a3b-2L.json").read_text())
    plan = json.loads((CONFIGS / cfg["plan_file"].split("/")[-1])
                      .read_text())
    bits = [(a["data_bits"], a["coeff_bits"]) for a in plan["layers"]]
    # 16 tokens of top-4 over 16 experts: the capacity factor, not the
    # top-k floor, sets the capacity (8), as a planted fault can halve it
    cfg.update(hidden_size=256, num_experts=16, num_experts_per_tok=4,
               moe_intermediate_size=64, seq_len=16)
    spec = MoEWorkloadSpec(layers=tuple(
        MoELayerSpec(d_ff_expert=64, num_experts=16, top_k=4,
                     data_bits=d, coeff_bits=c,
                     capacity_factor=cfg["capacity_factor"])
        for d, c in bits), d_model=256, seq_len=16)
    path = tmp_path / "moe.plan.json"
    save_plan(plan_moe_deployment(spec, "v5e", bit_candidates=None,
                                  on_infeasible="fallback"), path)
    cfg["plan_file"] = str(path)
    return cfg


def _cell(family, config):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    fam = harness.load_module(ROOT / "chipbench" / "families"
                              / f"{family}.py",
                              f"chipbench.families.{family}")
    return harness.Cell(f"test.{family}", 1, family, config, _traffic(),
                        fam, manifest["end_to_end"], [], {})


@pytest.fixture(params=["cnn", "moe"])
def cell(request, tmp_path):
    config = (_cnn_config() if request.param == "cnn"
              else _moe_config(tmp_path))
    return _cell(request.param, config)


def _run(cell, **kw):
    return harness.run(ROOT, cell, 2**31 + 5, 0.5, False, t_start=0.0,
                       require_chip=False, log=lambda m: None, **kw)


def test_program_reads_correct(cell):
    r = _run(cell)
    assert r["correct"], r["check"]
    assert list(r)[:3] == ["correct", "attempted", "failed"]
    assert list(r)[-1] == "check"
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"throughput", "p50_ms", "p95_ms",
                                 "setup_s"}
    assert set(r["check"]) == set(cell.config["check"]["limits"])
    # the program ran at the precision the configuration states
    assert jax.config.jax_default_matmul_precision == (
        cell.config.get("matmul_precision"))


def test_control_reads_not_correct(cell):
    r = _run(cell, control=True)
    assert not r["correct"], r["check"]


def test_answer_altered_where_it_is_produced(cell, monkeypatch):
    """The first answer of every dispatch is changed as the executable
    hands it back; with two callers that is about half of the answers,
    so the 12 checked ones hold some."""
    from repro.runtime import compiled
    cell.traffic["clients"] = 2
    run_bucket = compiled.CompiledModel._run_bucket

    def altered(self, xb, should_abort=None):
        out = run_bucket(self, xb, should_abort)
        delta = 1 if out.dtype.kind == "i" else 0.5
        return out.at[0].set(out[0] + delta)

    monkeypatch.setattr(compiled.CompiledModel, "_run_bucket", altered)
    r = _run(cell)
    assert not r["correct"], r["check"]


def test_capacity_halved_where_experts_are_routed(tmp_path, monkeypatch):
    """The program routes each block with half the per-block expert
    capacity the configuration states (the cut that would halve the
    expert buffers): the assignments past it drop out of a few tokens
    of each block."""
    import dataclasses
    from repro.runtime import workloads
    route = workloads._route_per_block

    def halved(p, x, cfg):
        moe = dataclasses.replace(cfg.moe,
                                  capacity_factor=cfg.moe.capacity_factor / 2)
        return route(p, x, dataclasses.replace(cfg, moe=moe))

    monkeypatch.setattr(workloads, "_route_per_block", halved)
    r = _run(_cell("moe", _moe_config(tmp_path)))
    assert not r["correct"], r["check"]
    assert r["check"]["tokens_off_share"]["value"] > (
        r["check"]["tokens_off_share"]["limit"])


def test_same_seed_same_inputs_and_weights(tmp_path):
    cfg = _cnn_config()
    fam = _cell("cnn", cfg).family
    a, b = fam.Model(cfg, 2**40 + 1, ROOT), fam.Model(cfg, 2**40 + 1, ROOT)
    c = fam.Model(cfg, 1, ROOT)
    assert all((x == y).all() for x, y in zip(a.params, b.params))
    assert all((x == y).all() for x, y in zip(a.inputs(3), b.inputs(3)))
    assert any((x != y).any() for x, y in zip(a.params, c.params))


def test_open_loop_mix_reads_correct():
    """The open-loop path a later Poisson cell would take: one
    submitter on a seeded schedule, latency from the scheduled send."""
    cell = _cell("cnn", _cnn_config())
    cell.traffic.update(loop="open", arrivals="poisson", rate_per_s=200.0)
    r = _run(cell)
    assert r["correct"], r["check"]
    assert r["attempted"] > 20 and r["failed"] == 0
    assert r["metrics"]["p50_ms"]["value"] > 0
