"""MoE dispatch: sort-based capacity routing vs dense oracle."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs import smoke_config
from repro.models import moe as moe_mod


def _cfg(top_k=2, experts=4, cf=8.0):
    cfg = smoke_config("qwen3-moe-30b-a3b").with_overrides(dtype="float32")
    return cfg.with_overrides(moe=dataclasses.replace(
        cfg.moe, num_experts=experts, top_k=top_k, capacity_factor=cf))


def test_dispatch_matches_dense_oracle():
    cfg = _cfg()
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    out, aux = moe_mod.moe_layer(p, x, cfg)
    ref = moe_mod.moe_layer_dense_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert float(aux) >= 0


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       top_k=st.integers(1, 3),
       experts=st.sampled_from([4, 8]))
def test_dispatch_property(seed, top_k, experts):
    """With generous capacity the sorted dispatch equals the dense path for
    random router/tokens."""
    cfg = _cfg(top_k=top_k, experts=experts, cf=float(experts))
    p = moe_mod.init_moe(jax.random.PRNGKey(seed), cfg)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                (1, 12, cfg.d_model))
    out, _ = moe_mod.moe_layer(p, x, cfg)
    ref = moe_mod.moe_layer_dense_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_capacity_drops_tokens():
    """At capacity_factor→0 the layer must drop most tokens (and stay
    finite) — switch-routing semantics."""
    cfg = _cfg(cf=0.25)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    out, aux = moe_mod.moe_layer(p, x, cfg)
    ref = moe_mod.moe_layer_dense_ref(p, x, cfg)
    assert bool(jnp.all(jnp.isfinite(out)))
    # dropped tokens → output differs from the no-drop oracle
    assert float(jnp.max(jnp.abs(out - ref))) > 1e-3


def test_shared_expert_path():
    cfg = smoke_config("llama4-maverick-400b-a17b") \
        .with_overrides(dtype="float32")
    cfg = cfg.with_overrides(moe=dataclasses.replace(
        cfg.moe, capacity_factor=8.0))
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    assert "shared_up" in p
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model))
    out, _ = moe_mod.moe_layer(p, x, cfg)
    ref = moe_mod.moe_layer_dense_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_aux_loss_prefers_balance():
    """Uniform routing must yield a lower aux loss than collapsed routing."""
    cfg = _cfg(top_k=1, experts=4)
    n, e = 64, 4
    balanced = jnp.tile(jnp.eye(e), (n // e, 1)) * 10.0
    collapsed = jnp.zeros((n, e)).at[:, 0].set(10.0)

    def aux_of(logits):
        probs = jax.nn.softmax(logits, axis=-1)
        _, ids = jax.lax.top_k(probs, 1)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(ids, e), axis=1), axis=0)
        return float(e * jnp.sum(me * ce))

    assert aux_of(balanced) < aux_of(collapsed)


def test_grouped_routing_matches_dense_oracle():
    """§Perf B2 path: group-local routing == dense oracle at high cap."""
    cfg = _cfg(cf=8.0).with_overrides(moe_groups=4)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    out, aux = moe_mod.moe_layer(p, x, cfg)
    ref = moe_mod.moe_layer_dense_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_shardmap_dispatch_combine_multidevice():
    """§Perf B4/B6 path on a real (4,2) mesh: shard_map dispatch/combine
    == dense oracle, and gradients flow (subprocess, 8 host devices)."""
    import subprocess
    import sys
    import textwrap
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import smoke_config
        from repro.launch.mesh import auto_mesh
        from repro.models import moe as moe_mod
        cfg = smoke_config('qwen3-moe-30b-a3b').with_overrides(
            dtype='float32')
        cfg = cfg.with_overrides(
            moe=dataclasses.replace(cfg.moe, capacity_factor=8.0),
            moe_groups=4, moe_combine_shardmap=True, moe_shard_hints=True)
        p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
        x = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                    (4, 16, cfg.d_model))
        mesh = auto_mesh((4, 2), ('data', 'model'))
        with mesh:
            out, _ = jax.jit(lambda p, x: moe_mod.moe_layer(p, x, cfg))(p, x)
            g = jax.jit(jax.grad(
                lambda p, x: moe_mod.moe_layer(p, x, cfg)[0].sum()))(p, x)
        ref = moe_mod.moe_layer_dense_ref(p, x, cfg)
        err = float(jnp.max(jnp.abs(out - ref)))
        gn = sum(float(jnp.sum(jnp.abs(l))) for l in jax.tree.leaves(g))
        assert err < 5e-3, err
        assert gn > 0
        print("SHARDMAP_MOE_OK", err)
    """)
    out = subprocess.run([sys.executable, "-c", prog], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert "SHARDMAP_MOE_OK" in out.stdout, out.stdout + out.stderr
