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
    """Group-local routing (four groups) == dense oracle at high cap."""
    cfg = _cfg(cf=8.0).with_overrides(moe_groups=4)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    out, aux = moe_mod.moe_layer(p, x, cfg)
    ref = moe_mod.moe_layer_dense_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_grouped_dispatch_under_gspmd_multidevice():
    """The grouped path on a real (4,2) mesh, the experts placed over
    ``model`` by ``parallel.sharding``'s rules and the tokens over
    ``data``: GSPMD's partitioning == dense oracle, and gradients flow
    (subprocess, 8 host devices)."""
    import subprocess
    import sys
    import textwrap
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import dataclasses, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.launch.mesh import auto_mesh
        from repro.models import moe as moe_mod
        from repro.parallel.sharding import ShardingRules
        cfg = smoke_config('qwen3-moe-30b-a3b').with_overrides(
            dtype='float32')
        cfg = cfg.with_overrides(
            moe=dataclasses.replace(cfg.moe, capacity_factor=8.0),
            moe_groups=4)
        p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
        x = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                    (4, 16, cfg.d_model))
        mesh = auto_mesh((4, 2), ('data', 'model'))
        place = ShardingRules(cfg, mesh, mode='tp').params_sharding(
            {'moe': p})['moe']
        assert place['w_up'].spec[0] == 'model', place['w_up'].spec
        ps = jax.device_put(p, place)
        xs = jax.device_put(x, NamedSharding(mesh, P('data')))
        out, _ = jax.jit(lambda p, x: moe_mod.moe_layer(p, x, cfg))(ps, xs)
        g = jax.jit(jax.grad(
            lambda p, x: moe_mod.moe_layer(p, x, cfg)[0].sum()))(ps, xs)
        ref = moe_mod.moe_layer_dense_ref(p, x, cfg)
        err = float(jnp.max(jnp.abs(out - ref)))
        gn = sum(float(jnp.sum(jnp.abs(l))) for l in jax.tree.leaves(g))
        assert err < 5e-3, err
        assert gn > 0
        print("GSPMD_MOE_OK", err)
    """)
    out = subprocess.run([sys.executable, "-c", prog], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert "GSPMD_MOE_OK" in out.stdout, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# a share of the experts, DeepSeek-V3's router (sigmoid, group-limited,
# corrected, scaled) and a shared expert, against a plain reference
# ---------------------------------------------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST
# d 64, 32 experts in 4 groups, top-4 from the best 2 groups, blocks of
# 16 tokens, no capacity as DeepSeek-V3 serves; capped at
# CAPPED = 2.0: capacity max(4, round(2·16·4/32)) = 4 per expert a block
EP = dict(num_experts=32, top_k=4, d_ff_expert=32, n_shared_experts=1,
          capacity_factor=None, scoring="sigmoid", n_group=4, topk_group=2,
          routed_scaling_factor=2.5)
D, S = 64, 16
CAPPED = 2.0


def _ep_cfg(groups=1, **kw):
    from repro.runtime.workloads import MoELayerSpec, MoEWorkloadSpec
    spec = MoEWorkloadSpec(layers=(MoELayerSpec(**{**EP, **kw}),),
                           d_model=D, seq_len=S)
    return dataclasses.replace(spec.layer_cfg(0), moe_groups=groups)


def _ep_params(seed=0):
    """Weights of the uncut layer (all 32 experts held)."""
    p = moe_mod.init_moe(jax.random.PRNGKey(seed), _ep_cfg())
    # a wider bias than the draw's, so that it moves many choices here
    p["router_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                               (EP["num_experts"],))
    return p


def _share(p, offset, held=8):
    """What one of the chips that split the layer holds of ``p``."""
    return {k: (v[offset:offset + held] if k.startswith("w_") else v)
            for k, v in p.items()}


def reference_layer(p, x, m, *, renorm_held=False):
    """The MoE layer over one token block ``x`` (S, d), plainly, in
    float32 at HIGHEST: no capacity buffer, no batching.  Routing over
    every expert as DeepSeek-V3 states it (sigmoid scores; the choice on
    scores plus the correction bias, inside the ``topk_group`` groups of
    best top-2 sum; the weights the chosen scores over their sum over all
    k, times the routed scaling).  Capacity follows the served rule: an
    assignment is kept while fewer than ``max(k, round(cf·S·k/E))``
    earlier assignments of the block (token order, then rank) went to
    its expert; the rule is per block because the served layer routes
    each block on its own.  Each held expert is one dense FFN over the
    block's tokens, masked by its kept assignments; the shared expert
    runs on every token.  Returns what the layer adds to ``x`` and the
    (routed to held, kept) counts.  ``renorm_held`` renormalizes over
    the held experts' weights alone (the wrong rule).  With no
    capacity every assignment is kept."""
    n, e, k = x.shape[0], m.num_experts, m.top_k
    offset, held = m.expert_offset, m.experts_held or e
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HIGHEST))
    c = s + p["router_bias"]
    per = e // m.n_group
    best = jnp.sort(c.reshape(n, m.n_group, per), axis=-1)[..., -2:].sum(-1)
    groups = jax.lax.top_k(best, m.topk_group)[1]
    allowed = jnp.zeros((n, m.n_group), bool).at[
        jnp.arange(n)[:, None], groups].set(True)
    ids = jax.lax.top_k(jnp.where(jnp.repeat(allowed, per, axis=1), c,
                                  -jnp.inf), k)[1]
    w = jnp.take_along_axis(s, ids, axis=1)
    mine = (ids >= offset) & (ids < offset + held)
    norm = jnp.where(mine, w, 0.0) if renorm_held else w
    w = w / jnp.sum(norm, axis=1, keepdims=True) * m.routed_scaling_factor
    keep = mine
    if m.capacity_factor is not None:
        cap = max(k, round(m.capacity_factor * n * k / e))
        flat = ids.reshape(-1)
        earlier = jnp.sum(jnp.tril(flat[:, None] == flat[None, :], k=-1),
                          -1)
        keep = (earlier < cap).reshape(ids.shape) & mine

    def ffn(gate, up, down):
        h = jnp.matmul(x, gate, precision=HIGHEST)
        return jnp.matmul(jax.nn.silu(h) * jnp.matmul(x, up,
                                                      precision=HIGHEST),
                          down, precision=HIGHEST)

    out = ffn(p["shared_gate"], p["shared_up"], p["shared_down"])
    for j in range(held):
        hit = (ids == offset + j) & keep
        wj = jnp.sum(jnp.where(hit, w, 0.0), axis=1, keepdims=True)
        out = out + wj * ffn(p["w_gate"][j], p["w_up"][j], p["w_down"][j])
    return out, (int(jnp.sum(mine)), int(jnp.sum(keep)))


def _blocks(seed=2, n=2):
    """Token blocks that share a topic within each, as served prompts do,
    so that capacity drops assignments."""
    kc, kz = jax.random.split(jax.random.PRNGKey(seed))
    c = jax.random.normal(kc, (n, 1, D))
    return 0.6 * c + 0.8 * jax.random.normal(kz, (n, S, D))


@pytest.mark.parametrize("cf", [None, CAPPED], ids=["dropless", "capped"])
@pytest.mark.parametrize("groups", [1, 2], ids=["one-block", "per-block"])
@pytest.mark.parametrize("offset, held", [(0, 8), (8, 8), (16, 8), (24, 8),
                                          (0, None)])
def test_held_share_matches_the_plain_reference(offset, held, groups, cf):
    """Each of the four shares of 8 experts, and the uncut layer, with no
    capacity and capped, against the reference block by block: the
    outputs, and the counts of assignments routed to held experts and
    kept."""
    p = _ep_params()
    cfg = _ep_cfg(groups, experts_held=held, expert_offset=offset,
                  capacity_factor=cf)
    mine = _share(p, offset, held or EP["num_experts"])
    x = _blocks(n=groups)
    with jax.default_matmul_precision("highest"):
        out, _, counts = moe_mod.moe_layer_counted(mine, x, cfg)
    assert counts.shape == (groups, 2)
    for b in range(groups):
        want, (routed, kept) = reference_layer(mine, x[b], cfg.moe)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        assert tuple(np.asarray(counts[b])) == (routed, kept)
    if held:
        assert 0 < int(counts[:, 1].sum()) <= int(counts[:, 0].sum())
    if cf is None:
        assert np.array_equal(counts[:, 0], counts[:, 1])


@pytest.mark.parametrize("cf", [None, CAPPED], ids=["dropless", "capped"])
def test_shares_add_up_to_the_uncut_layer(cf):
    """The four shares' outputs, with the shared expert (which every chip
    computes alike) counted once, add up to the uncut layer's; capped,
    capacity drops assignments, the same ones in both."""
    p = _ep_params()
    x = _blocks(n=2)
    cfg = _ep_cfg(2, capacity_factor=cf)
    with jax.default_matmul_precision("highest"):
        whole, _, counts = moe_mod.moe_layer_counted(p, x, cfg)
        shared = moe_mod._shared_ffn(p, x.reshape(-1, D), cfg)
        parts = []
        for offset in (0, 8, 16, 24):
            out, _, c = moe_mod.moe_layer_counted(
                _share(p, offset), x,
                _ep_cfg(2, experts_held=8, expert_offset=offset,
                        capacity_factor=cf))
            parts.append((out, c))
    total = sum(o for o, _ in parts) - 3 * shared.reshape(x.shape)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    assert np.array_equal(sum(np.asarray(c) for _, c in parts),
                          np.asarray(counts))
    kept, routed = int(counts[:, 1].sum()), int(counts[:, 0].sum())
    assert kept < routed if cf else kept == routed == x.shape[0] * S * 4


def test_dropless_runs_every_tile_a_load_fills():
    """An expert that takes more assignments than a tile holds runs as
    many tiles as they fill: a bias that puts expert 3 in every token's
    top-k sends it 160 assignments (two tiles of 128), and the layer
    still equals the dense oracle, with nothing dropped."""
    p = _share(_ep_params(), 0)
    p["router_bias"] = p["router_bias"].at[3].add(10.0)
    cfg = _ep_cfg(10, experts_held=8)
    x = _blocks(n=10)
    assert x.shape[0] * S > moe_mod.DROPLESS_TILE
    with jax.default_matmul_precision("highest"):
        out, _, counts = moe_mod.moe_layer_counted(p, x, cfg)
        ref = moe_mod.moe_layer_dense_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(counts[:, 0], counts[:, 1])
    assert int(counts[:, 0].sum()) >= x.shape[0] * S


def test_weights_are_normalized_over_all_top_k():
    """A share's weights are divided by the sum over every chosen
    expert, held or not: renormalizing over the held ones alone gives
    another answer, and the program's is the first."""
    p = _share(_ep_params(), 0)
    cfg = _ep_cfg(1, experts_held=8)
    x = _blocks(n=1)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(moe_mod.moe_layer(p, x, cfg)[0][0])
    right = np.asarray(reference_layer(p, x[0], cfg.moe)[0])
    wrong = np.asarray(reference_layer(p, x[0], cfg.moe,
                                       renorm_held=True)[0])
    np.testing.assert_allclose(out, right, rtol=1e-5, atol=1e-6)
    assert np.max(np.abs(out - wrong)) > 1e-2


@pytest.mark.parametrize("seed", [5, 7])
def test_sigmoid_router_chooses_on_the_bias_and_weighs_without_it(seed):
    """``route``: the bias moves the choice but not the weights, the
    choice stays inside the best groups by top-2 sum, and the weights
    are the chosen scores over their sum, times the scaling."""
    m = _ep_cfg().moe
    logits = jax.random.normal(jax.random.PRNGKey(seed), (S, 32))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 1), (32,))
    w, ids, s = moe_mod.route(logits, m, bias)
    w0, ids0, _ = moe_mod.route(logits, m, None)
    assert not np.array_equal(np.asarray(ids), np.asarray(ids0))
    chosen = np.take_along_axis(np.asarray(s), np.asarray(ids), axis=1)
    want = chosen / chosen.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), 2.5 * want, rtol=1e-6)
    c = np.asarray(s + bias).reshape(S, 4, 8)
    best = np.sort(c, axis=-1)[..., -2:].sum(-1)
    for t in range(S):
        kept = set(np.argsort(-best[t])[:2])
        assert {int(i) // 8 for i in np.asarray(ids[t])} <= kept
