"""Sharding rules: divisibility handling, fsdp wrap, opt-state specs, and a
real multi-device sharded train step (subprocess with 8 host devices)."""

import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import auto_mesh
from repro.models import build_model
from repro.parallel.sharding import ShardingRules, choose_mode


@pytest.fixture(scope="module")
def mesh():
    return auto_mesh((1, 1), ("data", "model"))


def _spec_of(tree_spec, *path):
    node = tree_spec
    for p in path:
        node = node[p]
    return node


def test_granite_mqa_head_not_sharded():
    """kv=1 head cannot shard over model=16 → replicated; q heads (48)
    don't divide 16 either... 48 % 16 == 0 so they do."""
    cfg = get_config("granite-20b")
    mesh = auto_mesh((1, 1), ("data", "model"))
    # emulate the production axis sizes through a fake mesh of size 1 but
    # checking the rule logic directly with tp_size patched
    rules = ShardingRules(cfg, mesh, mode="tp")
    rules.tp_size = 16
    model = build_model(cfg)
    shapes = model.init_abstract()
    spec = rules.params_spec(shapes)
    wq = _spec_of(spec, "stack", "s0", "attn", "wq")
    wk = _spec_of(spec, "stack", "s0", "attn", "wk")
    assert wq == P(None, None, "model", None)     # 48 heads ÷ 16 OK
    assert wk == P(None, None, None, None)        # 1 kv head: replicated


def test_gemma2_2b_heads_replicated():
    cfg = get_config("gemma2-2b")                  # 8 q heads < 16
    mesh = auto_mesh((1, 1), ("data", "model"))
    rules = ShardingRules(cfg, mesh, mode="tp")
    rules.tp_size = 16
    spec = rules.params_spec(build_model(cfg).init_abstract())
    assert _spec_of(spec, "stack", "s0", "attn", "wq") == \
        P(None, None, None, None)
    # but MLP hidden dim shards fine
    assert _spec_of(spec, "stack", "s0", "mlp", "w_up") == \
        P(None, None, "model")


def test_moe_expert_parallel_spec():
    cfg = get_config("qwen3-moe-30b-a3b")
    mesh = auto_mesh((1, 1), ("data", "model"))
    rules = ShardingRules(cfg, mesh, mode="tp")
    rules.tp_size = 16
    spec = rules.params_spec(build_model(cfg).init_abstract())
    assert _spec_of(spec, "stack", "s0", "moe", "w_up") == \
        P(None, "model", None, None)               # experts over model


def test_fsdp_adds_data_axis():
    cfg = get_config("llama4-maverick-400b-a17b")
    mesh = auto_mesh((1, 1), ("data", "model"))
    rules = ShardingRules(cfg, mesh, mode="fsdp")
    rules.tp_size = 16
    rules.dp_size = 16
    spec = rules.params_spec(build_model(cfg).init_abstract())
    wq = _spec_of(spec, "stack", "s0", "attn", "wq")
    assert "data" in jax.tree.leaves(wq) or "data" in str(wq)


def test_choose_mode_policy():
    mesh = auto_mesh((1, 1), ("data", "model"))

    class FakeShape(dict):
        pass
    small = get_config("llama3.2-3b")
    big = get_config("jamba-1.5-large-398b")
    # patch mesh.shape lookup via real small mesh: tp size 1 → everything
    # is "big"; use the production ratio directly instead
    assert choose_mode(big, mesh) == "fsdp"


def test_multidevice_sharded_step_runs():
    """8 host devices, (4,2) mesh: a sharded train step must produce the
    same loss as the single-device run (SPMD correctness end-to-end)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from repro.configs import smoke_config
        from repro.models import build_model
        from repro.optim import AdamWConfig, adamw_init
        from repro.parallel.sharding import ShardingRules
        from repro.train.step import make_train_step
        from repro.data import DataConfig
        from repro.data.pipeline import batch_at
        from repro.launch.mesh import auto_mesh

        cfg = smoke_config("qwen3-moe-30b-a3b").with_overrides(
            dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt_cfg = AdamWConfig()
        opt = adamw_init(params, opt_cfg)
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=8)
        batch = batch_at(dcfg, 0)
        step = make_train_step(model, opt_cfg)

        # single device reference
        l_ref = jax.jit(step)(params, opt, batch)[2]["loss"]

        mesh = auto_mesh((4, 2), ("data", "model"))
        rules = ShardingRules(cfg, mesh, mode="tp")
        psh = rules.to_sharding(rules.params_spec(
            jax.eval_shape(lambda: params)))
        osh = rules.to_sharding(rules.opt_spec(
            jax.eval_shape(lambda: opt),
            rules.params_spec(jax.eval_shape(lambda: params))))
        bsh = rules.to_sharding(rules.batch_spec(
            jax.eval_shape(lambda: batch)))
        with mesh:
            pp = jax.device_put(params, psh)
            oo = jax.device_put(opt, osh)
            bb = jax.device_put(batch, bsh)
            l_sh = jax.jit(step, in_shardings=(psh, osh, bsh),
                           out_shardings=(psh, osh, None))(
                pp, oo, bb)[2]["loss"]
        err = abs(float(l_ref) - float(l_sh))
        assert err < 1e-3, (float(l_ref), float(l_sh))
        print("SHARDED_OK", float(l_ref), float(l_sh))
    """)
    out = subprocess.run([sys.executable, "-c", prog], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_OK" in out.stdout, out.stdout + out.stderr
