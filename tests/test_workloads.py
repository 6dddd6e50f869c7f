"""The workload seam end-to-end: registry dispatch, the quantized-MoE
plan lifecycle (plan → save/load → AOT compile → gateway serve → mixed
fleet routing — each step the acceptance criteria name), and the
``sample_inputs``/``validate_input`` generalization with its deprecated
CNN-named shims."""

import asyncio
import dataclasses
import json

import numpy as np
import pytest

import repro.runtime as runtime
from repro.core.deploy import DeploymentError, DeploymentPlan, plan_config
from repro.runtime.compiled import CompiledCNN, validate_container_input
from repro.runtime.workloads import (CNNWorkloadSpec, CompiledMoE,
                                     MoELayerSpec, MoEWorkloadSpec,
                                     WorkloadSpec, _dense_ref_forward,
                                     _eager_forward, compile_plan,
                                     get_workload, list_workloads,
                                     moe_plan_spec, moe_workload_from_config,
                                     plan_moe_deployment, register_workload,
                                     validate_moe_plan, workload_spec)
from repro.serve.async_engine import AsyncCNNGateway, AsyncServeConfig
from repro.serve.cnn_engine import (CNNEngine, CNNServeConfig, ImageRequest,
                                    validate_image)


def tiny_moe_spec(n_layers=2, **kw):
    layer = MoELayerSpec(d_ff_expert=16, num_experts=4, top_k=2,
                         **{k: v for k, v in kw.items()
                            if k in ("data_bits", "coeff_bits",
                                     "n_shared_experts", "capacity_factor")})
    return MoEWorkloadSpec(layers=(layer,) * n_layers, d_model=8,
                           seq_len=8)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_builtin_kinds_registered():
    assert list_workloads() == ["cnn", "moe"]
    assert get_workload("cnn") is CNNWorkloadSpec
    assert get_workload("moe") is MoEWorkloadSpec


def test_unknown_kind_lists_registered():
    with pytest.raises(ValueError, match="cnn.*moe"):
        get_workload("ssm")


def test_reregistering_kind_rejected():
    with pytest.raises(ValueError, match="already registered"):
        @register_workload
        class Impostor(WorkloadSpec):
            kind = "moe"


def test_abstract_kind_rejected():
    with pytest.raises(ValueError, match="concrete kind"):
        @register_workload
        class NoKind(WorkloadSpec):
            pass


def test_workload_spec_wraps_cnn_plans():
    plan = _cnn_plan()
    spec = workload_spec(plan)
    assert isinstance(spec, CNNWorkloadSpec)
    assert spec.cnn == plan.cnn


# ---------------------------------------------------------------------------
# MoE plan lifecycle: plan → round-trip → compile → validate
# ---------------------------------------------------------------------------

def test_moe_plan_round_trips_save_load(tmp_path):
    plan = plan_moe_deployment(tiny_moe_spec(), "v5e")
    assert plan.feasible and plan.cnn is None
    assert plan.workload.kind == "moe"
    path = runtime.save_plan(plan, tmp_path / "moe_plan.json")
    loaded = runtime.load_plan(path)
    assert loaded == plan
    assert json.loads(path.read_text())["workload"]["kind"] == "moe"


def test_moe_planner_picks_highest_precision_that_fits():
    plan = plan_moe_deployment(tiny_moe_spec(), "v5e")
    # the tiny workload fits v5e at the widest candidate precision
    assert plan.bits() == [(12, 10)] * 2
    spec = moe_plan_spec(plan)
    assert [(s.data_bits, s.coeff_bits) for s in spec.layers] \
        == plan.bits()


def test_moe_plan_infeasible_on_edge_feasible_on_v5e():
    """The plan-aware placement story: a real MoE workload exceeds the
    edge part's budgets but fits a v5e — which is exactly what keeps
    MoE plans off edge workers in a mixed fleet."""
    spec = MoEWorkloadSpec(
        layers=(MoELayerSpec(d_ff_expert=128, num_experts=8, top_k=2),),
        d_model=64, seq_len=32)
    assert plan_moe_deployment(spec, "v5e").feasible
    with pytest.raises(DeploymentError, match="does not fit device 'edge'"):
        plan_moe_deployment(spec, "edge")
    fallback = plan_moe_deployment(spec, "edge", on_infeasible="fallback")
    assert not fallback.feasible


def test_moe_plan_config_raises_with_kind():
    plan = plan_moe_deployment(tiny_moe_spec(), "v5e")
    with pytest.raises(ValueError, match="'moe' workload"):
        plan_config(plan)


def test_compiled_moe_matches_eager_and_tracks_dense_ref():
    """validate_plan's MoE twin: the bucketed AOT path is numerically
    the eager quantized stack, and quantization stays within tolerance
    of the dense float oracle."""
    plan = plan_moe_deployment(tiny_moe_spec(), "v5e")
    v = validate_moe_plan(plan)
    assert v.compiled_matches_eager
    assert v.dense_ref_rel_err < 0.15
    assert v.quant_error == plan.quant_error


def test_coarser_bits_raise_quant_error():
    fine = tiny_moe_spec(data_bits=12, coeff_bits=10)
    coarse = tiny_moe_spec(data_bits=4, coeff_bits=4)
    fine_err = plan_moe_deployment(fine, "v5e", bit_candidates=None)
    coarse_err = plan_moe_deployment(coarse, "v5e", bit_candidates=None)
    assert coarse_err.quant_error > fine_err.quant_error


def test_compile_plan_dispatches_by_kind():
    moe = compile_plan(plan_moe_deployment(tiny_moe_spec(), "v5e"),
                       max_batch=2)
    cnn = compile_plan(_cnn_plan(), max_batch=2)
    assert isinstance(moe, CompiledMoE) and moe.kind == "moe"
    assert isinstance(cnn, CompiledCNN) and cnn.kind == "cnn"
    assert moe.stats()["kind"] == "moe"


# many experts at a tight capacity factor: expert capacity binds, so a
# block that shared its routing with its batch would lose tokens to it
BINDING_CAPACITY = MoEWorkloadSpec(
    layers=(MoELayerSpec(d_ff_expert=16, num_experts=16, top_k=4,
                         capacity_factor=0.5),), d_model=8, seq_len=8)


@pytest.mark.parametrize("spec", [tiny_moe_spec(), BINDING_CAPACITY],
                         ids=["tiny", "binding_capacity"])
def test_compiled_moe_bucketing_and_chunking(spec):
    """Padding to a bucket and chunking past max_batch must not change
    any request's output (the CompiledCNN contract, on the MoE backend):
    each token block is routed on its own, so neither padding nor the
    other blocks of a dispatch compete for its expert capacity."""
    plan = plan_moe_deployment(spec, "v5e")
    compiled = compile_plan(plan, max_batch=4)
    xs = np.stack(compiled.sample_inputs(7, seed=3))
    y_all = np.asarray(compiled(xs))        # chunks 4 + 3(pad to 4)
    singles = np.stack([np.asarray(compiled(x)) for x in xs])
    np.testing.assert_allclose(y_all, singles, rtol=1e-5, atol=1e-5)
    eager = np.asarray(_eager_forward(compiled.spec, compiled.params, xs))
    np.testing.assert_allclose(y_all, eager, rtol=1e-5, atol=1e-5)
    assert sum(compiled.bucket_hits.values()) > 0


def test_moe_validate_input_rejects():
    compiled = compile_plan(plan_moe_deployment(tiny_moe_spec(), "v5e"),
                            max_batch=2, warmup=False)
    with pytest.raises(ValueError, match="token block shape"):
        compiled.validate_input(np.zeros((3, 3), np.float32))
    bad = np.zeros(compiled.in_shape, np.float32)
    bad[0, 0] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        compiled.validate_input(bad)
    with pytest.raises(ValueError, match="dtype"):
        compiled.validate_input(
            np.zeros(compiled.in_shape, np.complex64))


# ---------------------------------------------------------------------------
# serving: sync engine + async gateway, plan-type-blind
# ---------------------------------------------------------------------------

def _cnn_plan():
    from repro.core.cnn import CNNConfig, ConvLayerSpec
    from tests.test_plan_golden import _golden_plan
    plan = _golden_plan()
    # shrink to a fast-compiling network for serve tests
    cnn = CNNConfig(layers=(
        ConvLayerSpec(1, 2, data_bits=6, coeff_bits=4, shift=5,
                      block="conv1"),), img_h=16, img_w=16)
    return dataclasses.replace(
        plan, cnn=cnn,
        layers=(dataclasses.replace(plan.layers[1], index=0),))


def test_sync_engine_serves_moe_plan():
    plan = plan_moe_deployment(tiny_moe_spec(), "v5e")
    eng = CNNEngine.from_plan(plan, serve_cfg=CNNServeConfig(max_batch=2))
    xs = eng.compiled.sample_inputs(3, seed=1)
    reqs = [ImageRequest(image=x, request_id=i) for i, x in enumerate(xs)]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert reqs[0].output.shape == eng.compiled.in_shape
    # admission rejects a CNN-shaped payload on the MoE plan
    with pytest.raises(ValueError, match="token block shape"):
        eng.submit(ImageRequest(image=np.zeros((8, 8, 1), np.int8)))


def test_gateway_serves_moe_and_cnn_side_by_side():
    """The acceptance path: one AsyncCNNGateway serving a CNN plan and
    a quantized MoE plan concurrently, each validating its own input
    contract, sharing one ExecutableCache."""
    async def main():
        gw = AsyncCNNGateway(AsyncServeConfig(max_batch=2, max_pending=16))
        gw.register_plan(_cnn_plan(), plan_id="cnn")
        gw.register_plan(plan_moe_deployment(tiny_moe_spec(), "v5e"),
                         plan_id="moe")
        assert gw.plans["cnn"].kind == "cnn"
        assert gw.plans["moe"].kind == "moe"
        async with gw:
            cnn_in = gw.plans["cnn"].compiled.sample_inputs(2, seed=0)
            moe_in = gw.plans["moe"].compiled.sample_inputs(2, seed=0)
            futs = [await gw.submit(x, plan_id="cnn") for x in cnn_in]
            futs += [await gw.submit(x, plan_id="moe") for x in moe_in]
            outs = await asyncio.gather(*futs)
            assert outs[0].shape == gw.plans["cnn"].compiled.in_shape[:2] \
                + (2,)
            assert outs[2].shape == gw.plans["moe"].compiled.in_shape
            # per-plan admission: an MoE block is rejected on the CNN
            # plan and vice versa, each with its workload's noun
            with pytest.raises(ValueError, match="image shape"):
                await gw.submit(moe_in[0], plan_id="cnn")
            with pytest.raises(ValueError, match="token block shape"):
                await gw.submit(cnn_in[0], plan_id="moe")
        assert gw.served == 4
    asyncio.run(main())


def test_moe_plans_share_exec_cache_across_gateway_plans():
    async def main():
        gw = AsyncCNNGateway(AsyncServeConfig(max_batch=2))
        plan = plan_moe_deployment(tiny_moe_spec(), "v5e")
        gw.register_plan(plan, plan_id="moe-a")
        before = gw.plans["moe-a"].compiled.compiles
        gw.register_plan(plan, plan_id="moe-b", key=None)
        # identical layer specs: the second registration compiles nothing
        assert gw.plans["moe-b"].compiled.compiles == 0
        assert before > 0
        await gw.close()
    asyncio.run(main())


# ---------------------------------------------------------------------------
# mixed CNN+MoE fleet: plan-aware placement honors workload hosting
# ---------------------------------------------------------------------------

def test_fleet_routes_mixed_cnn_and_moe_plans():
    """The last acceptance step: a live Fleet with an edge worker that
    only hosts the CNN plan (the MoE plan is infeasible on edge — see
    ``test_moe_plan_infeasible_on_edge_feasible_on_v5e``) and a v5e
    worker hosting both.  MoE traffic must route exclusively to the
    v5e; CNN traffic may use either; both kinds complete."""
    from repro.fleet import Fleet, FleetWorker, NoWorkerAvailable

    cnn_plan = _cnn_plan()
    moe_plan = plan_moe_deployment(tiny_moe_spec(), "v5e")

    def gateway(plans):
        gw = AsyncCNNGateway(AsyncServeConfig(max_batch=2, max_pending=16))
        for pid, plan in plans:
            gw.register_plan(plan, plan_id=pid)
        return gw

    async def main():
        edge = FleetWorker("edge0", gateway([("cnn", cnn_plan)]), "edge")
        v5e = FleetWorker("v5e0", gateway([("cnn", cnn_plan),
                                           ("moe", moe_plan)]), "v5e")
        assert edge.workload_kinds == {"cnn"}
        assert v5e.workload_kinds == {"cnn", "moe"}
        fleet = Fleet([edge, v5e], router="plan_aware")
        async with fleet:
            cnn_in = v5e.gateway.plans["cnn"].compiled.sample_inputs(
                4, seed=0)
            moe_in = v5e.gateway.plans["moe"].compiled.sample_inputs(
                4, seed=0)
            futs = [await fleet.submit(x, plan_id="cnn") for x in cnn_in]
            futs += [await fleet.submit(x, plan_id="moe") for x in moe_in]
            outs = await asyncio.gather(*futs)
            assert all(o is not None for o in outs)
            stats = fleet.stats()
            assert stats["workers"]["edge0"]["workloads"] == ["cnn"]
            assert stats["workers"]["v5e0"]["workloads"] == ["cnn", "moe"]
            # every MoE request was served by the v5e gateway
            assert v5e.gateway.plans["moe"].served == 4
            # draining the only MoE-capable worker makes MoE traffic
            # unroutable while CNN traffic still flows to the edge
            v5e.draining = True
            with pytest.raises(NoWorkerAvailable):
                fleet.submit_nowait(moe_in[0], plan_id="moe")
            fut = await fleet.submit(cnn_in[0], plan_id="cnn")
            assert (await fut) is not None
    asyncio.run(main())


# ---------------------------------------------------------------------------
# sample_inputs / validate_input seam + deprecated shims
# ---------------------------------------------------------------------------

def test_cnn_sample_inputs_and_deprecated_sample_images():
    compiled = compile_plan(_cnn_plan(), max_batch=2, warmup=False)
    fresh = compiled.sample_inputs(2, seed=7)
    with pytest.deprecated_call():
        legacy = compiled.sample_images(2, seed=7)
    np.testing.assert_array_equal(np.stack(fresh), np.stack(legacy))


def test_validate_image_shim_warns_and_delegates():
    with pytest.deprecated_call():
        out = validate_image(np.zeros((8, 8, 1), np.int8), (8, 8, 1),
                             np.int8)
    assert out.shape == (8, 8, 1)
    with pytest.deprecated_call():
        with pytest.raises(ValueError, match="container range"):
            validate_image(np.full((8, 8, 1), 300), (8, 8, 1), np.int8)


def test_validate_container_input_noun():
    with pytest.raises(ValueError, match="patch shape"):
        validate_container_input(np.zeros((2, 2), np.int8), (8, 8, 1),
                                 np.int8, noun="patch")


_RANGE_ERR = ("request 7: image values outside the int8 container range "
              "[-128, 127] — would wrap, not clamp")
_FLOAT_ERR = ("request 7: image dtype {} carries non-integral values — "
              "quantize explicitly (e.g. ops.quantize_fixed) before "
              "submitting")


@pytest.mark.parametrize("x, error", [
    (np.full((4, 4, 2), -128, np.int8), None),
    (np.full((4, 4, 2), 200, np.uint8), _RANGE_ERR),
    (np.full((4, 4, 2), 127, np.uint8), None),
    (np.arange(-128, -96, dtype=np.int16).reshape(4, 4, 2), None),
    (np.full((4, 4, 2), -129, np.int16), _RANGE_ERR),
    (np.full((4, 4, 2), 127.0, np.float32), None),
    (np.full((4, 4, 2), 128.0), _RANGE_ERR),
    (np.full((4, 4, 2), 0.5, np.float32), _FLOAT_ERR.format("float32")),
    (np.full((4, 4, 2), np.inf), _FLOAT_ERR.format("float64")),
    (np.full((4, 4, 2), np.nan, np.float32), _FLOAT_ERR.format("float32")),
    (np.zeros((4, 2, 4), np.int8),
     "request 7: image shape (4, 2, 4) != engine input (4, 4, 2)"),
], ids=["int8-in-int8", "uint8-200", "uint8-127", "int16-in-range", "int16-below",
        "float-integral", "float-above", "float-non-integral", "inf", "nan",
        "wrong-shape"])
def test_validate_container_input_verdicts(x, error):
    """One verdict per input: accepted inputs come back with their
    values, rejected ones raise the admission error word for word."""
    if error is None:
        out = validate_container_input(x, (4, 4, 2), np.int8, 7,
                                       noun="image")
        np.testing.assert_array_equal(out, x)
        assert out.dtype == x.dtype
    else:
        with pytest.raises(ValueError) as e:
            validate_container_input(x, (4, 4, 2), np.int8, 7, noun="image")
        assert str(e.value) == error


def test_validate_image_shim_keeps_the_image_noun_and_request_id():
    """The legacy name must keep producing legacy-shaped errors: the
    noun is ``image`` (not the generic ``input``) and the request id
    callers passed still lands in the message."""
    with pytest.deprecated_call():
        with pytest.raises(ValueError, match=r"request 7: image shape"):
            validate_image(np.zeros((2, 2), np.int8), (8, 8, 1),
                           np.int8, request_id=7)


def test_validate_image_shim_reexported_from_repro_serve():
    """PR-8 moved the engine module but the public ``repro.serve``
    surface still re-exports the shim (callers import it from there)."""
    import repro.serve as serve
    assert serve.validate_image is validate_image
    assert "validate_image" in serve.__all__


def test_sample_images_shim_seed_determinism_and_default():
    """``sample_images`` must keep its full signature contract through
    the shim: same seed ⇒ same draw as ``sample_inputs``, default seed
    included, and every call warns."""
    compiled = compile_plan(_cnn_plan(), max_batch=2, warmup=False)
    with pytest.deprecated_call():
        default = compiled.sample_images(1)
    np.testing.assert_array_equal(default[0],
                                  compiled.sample_inputs(1, seed=0)[0])
    with pytest.deprecated_call():
        a = compiled.sample_images(3, seed=11)
    with pytest.deprecated_call():
        b = compiled.sample_images(3, seed=11)
    np.testing.assert_array_equal(np.stack(a), np.stack(b))
    # the shimmed draws admit through the modern validation seam
    for img in a:
        compiled.validate_input(img)


def test_shim_warnings_name_the_replacement():
    """The deprecation text must point at the successor API — that's
    what makes the migration self-serve."""
    compiled = compile_plan(_cnn_plan(), max_batch=1, warmup=False)
    with pytest.warns(DeprecationWarning, match="sample_inputs"):
        compiled.sample_images(1)
    with pytest.warns(DeprecationWarning, match="validate_input"):
        validate_image(np.zeros((8, 8, 1), np.int8), (8, 8, 1), np.int8)


# ---------------------------------------------------------------------------
# config-zoo bridge
# ---------------------------------------------------------------------------

def test_moe_workload_from_config():
    from repro.configs import smoke_config
    cfg = smoke_config("qwen3-moe-30b-a3b")
    spec = moe_workload_from_config(cfg, n_layers=1, seq_len=4)
    assert spec.d_model == cfg.d_model
    assert spec.layers[0].num_experts == cfg.moe.num_experts
    plan = plan_moe_deployment(spec, "v5e")
    assert plan.feasible


def test_moe_workload_from_dense_config_raises():
    from repro.configs import smoke_config
    cfg = smoke_config("llama3.2-3b")
    with pytest.raises(ValueError, match="no MoE block"):
        moe_workload_from_config(cfg)


# ---------------------------------------------------------------------------
# a layer holding a share of its experts, with DeepSeek-V3's router and a
# shared expert: plan fields, served path against the plain reference,
# counters, demand
# ---------------------------------------------------------------------------

def ep_spec(n_layers=2, **kw):
    """d 64, 32 experts in 4 groups, top-4 from the best 2, 8 held,
    one shared expert, no capacity, blocks of 16 tokens."""
    from test_moe import EP
    layer = MoELayerSpec(**{**EP, "experts_held": 8, "data_bits": 8,
                            "coeff_bits": 6, **kw})
    return MoEWorkloadSpec(layers=(layer,) * n_layers, d_model=64,
                           seq_len=16)


def _plan_text(path):
    text = path.read_text()
    return text if text.endswith("\n") else text + "\n"


def test_routing_fields_round_trip_through_the_plan():
    plan = plan_moe_deployment(ep_spec(expert_offset=8), "v5e",
                               bit_candidates=None, on_infeasible="fallback")
    back = DeploymentPlan.from_json(plan.to_json())
    assert back == plan and back.workload == plan.workload
    layer = json.loads(plan.to_json())["workload"]["spec"]["layers"][0]
    assert {k: layer[k] for k in ("scoring", "n_group", "topk_group",
                                  "routed_scaling_factor", "experts_held",
                                  "expert_offset")} == {
        "scoring": "sigmoid", "n_group": 4, "topk_group": 2,
        "routed_scaling_factor": 2.5, "experts_held": 8, "expert_offset": 8}
    assert layer["capacity_factor"] is None
    # a default layer's payload carries the keys it always had
    plain = MoEWorkloadSpec(layers=(MoELayerSpec(16, 4, 2),), d_model=8)
    assert set(plain.to_payload()["layers"][0]) == {
        "d_ff_expert", "num_experts", "top_k", "data_bits", "coeff_bits",
        "n_shared_experts", "capacity_factor"}


@pytest.mark.parametrize("path", [
    "tests/golden/plan_moe_golden.json",
    "chipbench/configs/qwen3-moe-30b-a3b-2L.plan.json"])
def test_committed_moe_plans_read_unchanged(path):
    from pathlib import Path
    p = Path(__file__).resolve().parents[1] / path
    plan = DeploymentPlan.from_json(p.read_text())
    assert plan.to_json() + "\n" == _plan_text(p)
    assert all(s.routing() == {} and s.held == s.num_experts
               for s in plan.workload.layers)


def _fq(x, bits):
    hi = float((1 << (bits - 1)) - 1)
    s = hi / np.maximum(np.max(np.abs(x), axis=-1, keepdims=True), 1e-6)
    return np.round(x * s) / s


@pytest.mark.parametrize("cf", [None, 2.0], ids=["dropless", "capped"])
def test_compiled_held_layers_serve_the_plain_reference(cf):
    """Two held-share layers through ``compile_plan`` (a padded bucket of
    4 for 3 requests, then a bucket of 1) against the plain reference
    block by block; the counters sum the live blocks' counts only, and
    the executables carry the routing fields in their name and key."""
    import jax
    from test_moe import reference_layer
    spec = ep_spec(capacity_factor=cf)
    plan = plan_moe_deployment(spec, "v5e", bit_candidates=None,
                               on_infeasible="fallback")
    with jax.default_matmul_precision("highest"):
        moe = compile_plan(plan, key=jax.random.PRNGKey(4), max_batch=4)
        rng = np.random.default_rng(1)
        xs = rng.standard_normal((4, 16, 64)).astype(np.float32)
        got = np.concatenate([np.asarray(moe(xs[:3])),
                              np.asarray(moe(xs[3:]))])
        act, routed, kept = xs.copy(), 0, 0
        for i, s in enumerate(moe.spec.layers):
            cfg = moe.spec.layer_cfg(i)
            for b in range(len(act)):
                add, (r, k) = reference_layer(moe.params[i],
                                              _fq(act[b], s.data_bits),
                                              cfg.moe)
                act[b] = act[b] + np.asarray(add)
                routed, kept = routed + r, kept + k
    np.testing.assert_allclose(got, act, rtol=1e-5, atol=1e-5)
    st = moe.stats()
    assert (st["moe_routed_held"], st["moe_kept_held"]) == (routed, kept)
    assert 0 < kept < routed if cf else 0 < kept == routed
    assert st["padded_rows"] == 1
    assert moe.params[0]["w_up"].shape == (8, 64, 32)
    assert moe.params[0]["router"].shape == (64, 32)
    text = moe._compile_layer(0, 4).as_text()
    assert text.split(",")[0] == (
        "HloModule jit_moe_e32_k4_d8c6_sigmoid_g4_t2_x2p5_h8")
    assert all(f"/{scope}/" in text for scope in (
        "router", "dispatch", "held_ffn", "shared_ffn", "combine"))
    assert moe._layer_key(0, 4)[12:-2] == (
        ("scoring", "sigmoid"), ("n_group", 4), ("topk_group", 2),
        ("routed_scaling_factor", 2.5), ("experts_held", 8))


def test_a_default_layer_keeps_its_key_and_a_cnn_counts_nothing():
    moe = CompiledMoE(tiny_moe_spec(1), tiny_moe_spec(1).init_params(
        __import__("jax").random.PRNGKey(0)), max_batch=2)
    s = moe.spec.layers[0]
    assert moe._layer_key(0, 2) == (
        "moe_ffn", 8, 16, 4, 2, 0, 2.0, s.data_bits, s.coeff_bits, 8,
        "silu", True, None, 2)
    moe(np.zeros((2, 8, 8), np.float32))
    # a zero block routes its 8 tokens × top-2 by the router's ties alone
    assert moe.stats()["moe_routed_held"] == 2 * 8 * 2
    cnn = compile_plan(_cnn_plan(), max_batch=2)
    cnn(np.stack(cnn.sample_inputs(2)))
    assert "moe_kept_held" not in cnn.stats()


def test_moe_layer_demand_counts_held_experts_only():
    """Weights and the VMEM working set of a layer holding 8 of 32
    experts are those of the 8 (and the shared expert's weights); the
    routed MACs are the held experts' share."""
    from repro.runtime.workloads import moe_layer_demand
    spec = ep_spec(1)
    held = spec.layers[0]
    whole = dataclasses.replace(held, experts_held=None)
    d, fe, S, k, e = 64, 32, 16, 4, 32
    a = moe_layer_demand(spec, held, 8, 4)
    b = moe_layer_demand(spec, whole, 8, 4)
    assert a["hbm_bytes"] == (3 * 8 * d * fe + 3 * d * fe) * 4 / 8 \
        + S * d * 8 / 8
    assert b["hbm_bytes"] == (3 * 32 * d * fe + 3 * d * fe) * 4 / 8 \
        + S * d * 8 / 8
    # capped at 2.0: 4 rows an expert; dropless: one tile
    cap = 4
    capped = moe_layer_demand(spec, dataclasses.replace(
        held, capacity_factor=2.0), 8, 4)
    assert capped["vmem_bytes"] == 8 * cap * d * 4 + 8 * d * fe * 4
    assert a["vmem_bytes"] == 128 * d * 4 + 8 * d * fe * 4
    assert b["vmem_bytes"] == 128 * d * 4 + 32 * d * fe * 4
    assert a["mxu_cost"] == S * d * e + 3 * S * k * 8 / e * d * fe \
        + 3 * S * d * fe


@pytest.mark.parametrize("kw", [
    {"experts_held": 0}, {"experts_held": 33},
    {"experts_held": 8, "expert_offset": 25}, {"scoring": "relu"},
    {"n_group": 5}, {"topk_group": 5}, {"topk_group": 0},
    {"scoring": "softmax"},          # group-limited top-k without sigmoid
    {"top_k": 20},                   # more than the 2 kept groups hold
    {"capacity_factor": 0.0},
])
def test_invalid_routing_fields_are_refused(kw):
    with pytest.raises(ValueError):
        ep_spec(1, **kw)
