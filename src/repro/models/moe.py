"""Mixture-of-Experts with sort-based top-k dispatch under a capacity bound.

Dispatch never materializes the O(tokens × experts × capacity) one-hot
tensor of the classic einsum formulation: assignments are ranked inside
their expert via a single argsort + bincount, then scattered into a dense
(experts × capacity, d_model) buffer that feeds one batched expert matmul.
Tokens beyond capacity are dropped (standard switch-style routing); the
combine step re-weights by the router probability and sums the surviving
top-k paths.

Expert parallelism: the expert axis of w_up/w_gate/w_down is sharded over
the ``model`` mesh axis (see parallel/sharding.py); the scatter/gather pair
is GSPMD's to schedule in the baseline, and is replaced by an explicit
``shard_map`` + ``all_to_all`` in the optimized path (§Perf).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import _act, dense_init, split_keys


def init_moe(key, cfg):
    m = cfg.moe
    d, fe, e = cfg.d_model, m.d_ff_expert, m.num_experts
    dt = cfg.jnp_dtype
    ks = split_keys(key, 7)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "w_up": dense_init(ks[1], (e, d, fe), dt, fan_in=d),
        "w_down": dense_init(ks[2], (e, fe, d), dt, fan_in=fe),
    }
    if cfg.mlp_gated:
        p["w_gate"] = dense_init(ks[3], (e, d, fe), dt, fan_in=d)
    if m.n_shared_experts:
        fs = fe * m.n_shared_experts
        p["shared_up"] = dense_init(ks[4], (d, fs), dt)
        p["shared_down"] = dense_init(ks[5], (fs, d), dt, fan_in=fs)
        if cfg.mlp_gated:
            p["shared_gate"] = dense_init(ks[6], (d, fs), dt)
    return p


def quantize_moe_params(p, coeff_bits: int):
    """Fake-quantize the expert/shared FFN weights onto the symmetric
    ``coeff_bits``-bit fixed-point grid (per-tensor scale, mirroring
    ``ops.quantize_fixed``'s range): each tensor is scaled so its max
    magnitude maps to ``2^(c-1) - 1``, rounded, and scaled back — the
    values a ``coeff_bits``-wide container deployment would compute
    with, kept in float for the TPU matmuls.  The router projection is
    left exact: expert *choice* is control flow, and mis-rounding it
    swaps which experts run instead of adding bounded rounding noise
    (the serving planner quantizes compute, not routing).
    """
    hi = float((1 << (coeff_bits - 1)) - 1)

    def q(w):
        s = hi / jnp.maximum(jnp.max(jnp.abs(w)), 1e-9)
        return (jnp.round(w * s) / s).astype(w.dtype)

    return {k: (v if k == "router" else q(v)) for k, v in p.items()}


def _top_k(logits, k):
    vals, ids = jax.lax.top_k(logits, k)
    return vals, ids


def _hint(x, spec_axes, enable):
    """§Perf sharding hint: without it GSPMD replicates the (E, C, D)
    expert buffers across the data axis and every data rank computes every
    expert — the dominant waste in the MoE baselines (EXPERIMENTS §Perf)."""
    if not enable:
        return x
    from jax.sharding import PartitionSpec as P
    try:
        from jax._src.mesh import thread_resources
        names = thread_resources.env.physical_mesh.axis_names
        if "pod" in names:   # multi-pod: data-parallel axes are (pod, data)
            spec_axes = [("pod", "data") if a == "data" else a
                         for a in spec_axes]
        return jax.lax.with_sharding_constraint(x, P(*spec_axes))
    except Exception:
        return x   # no mesh (single-device tests)


def moe_layer(p, x, cfg):
    if cfg.moe_groups > 1:
        return moe_layer_grouped(p, x, cfg)
    return _moe_layer_flat(p, x, cfg)


def _moe_layer_flat(p, x, cfg):
    """x: (B,S,D) -> (out (B,S,D), aux_loss scalar)."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    e, k = m.num_experts, m.top_k
    xf = x.reshape(n, d)

    with jax.named_scope("router"):
        router_logits = xf.astype(jnp.float32) @ p["router"]      # (N,E)
        probs = jax.nn.softmax(router_logits, axis=-1)
        top_vals, top_ids = _top_k(probs, k)                      # (N,k)
        top_vals = top_vals / jnp.clip(
            jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)      # renorm

        # ---- load-balancing auxiliary loss (switch-style) ------------
        me = jnp.mean(probs, axis=0)                              # (E,)
        ce = jnp.mean(
            jnp.sum(jax.nn.one_hot(top_ids, e, dtype=jnp.float32),
                    axis=1), axis=0)
        aux = e * jnp.sum(me * ce) * m.router_aux_weight

    hints = cfg.moe_shard_hints
    with jax.named_scope("dispatch"):
        # ---- sort-based rank-within-expert ---------------------------
        capacity = int(max(k, round(m.capacity_factor * n * k / e)))
        flat_ids = top_ids.reshape(-1)                            # (N*k,)
        sort_idx = jnp.argsort(flat_ids)                          # stable
        sorted_ids = flat_ids[sort_idx]
        counts = jnp.bincount(flat_ids, length=e)                 # (E,)
        starts = jnp.cumsum(counts) - counts                      # exclusive
        ranks_sorted = jnp.arange(n * k) - starts[sorted_ids]
        ranks = jnp.zeros_like(ranks_sorted).at[sort_idx].set(ranks_sorted)

        keep = ranks < capacity
        slot = jnp.where(keep, flat_ids * capacity + ranks, e * capacity)

        # ---- scatter tokens into the expert buffer -------------------
        token_of = jnp.repeat(jnp.arange(n), k)                   # (N*k,)
        buf = jnp.zeros((e * capacity + 1, d), x.dtype)
        buf = buf.at[slot].set(xf[token_of], mode="drop")
        expert_in = _hint(buf[:-1].reshape(e, capacity, d),
                          ("model", "data", None), hints)

    with jax.named_scope("expert_ffn"):
        # batched over experts
        h = jnp.einsum("ecd,edf->ecf", expert_in, p["w_up"])
        if "w_gate" in p:
            h = _act(jnp.einsum("ecd,edf->ecf", expert_in, p["w_gate"]),
                     cfg.act) * h
        else:
            h = _act(h, cfg.act)
        h = _hint(h, ("model", "data", None), hints)
        expert_out = _hint(jnp.einsum("ecf,efd->ecd", h, p["w_down"]),
                           ("model", "data", None), hints)

    with jax.named_scope("combine"):
        # gather surviving assignments back
        flat_out = expert_out.reshape(e * capacity, d)
        gathered = jnp.where(
            keep[:, None], flat_out[jnp.minimum(slot, e * capacity - 1)],
            jnp.zeros((), x.dtype))                                # (N*k, D)
        gathered = _hint(gathered, ("data", None), hints)
        # fused f32 contraction over k — never materializes an f32 (N·k, D)
        out = jnp.einsum("nkd,nk->nd", gathered.reshape(n, k, d),
                         top_vals.astype(jnp.float32),
                         preferred_element_type=jnp.float32).astype(x.dtype)
        out = _hint(out, ("data", None), hints)

    # ---- shared experts (always-on path) ------------------------------
    if "shared_up" in p:
        hs = xf @ p["shared_up"]
        if "shared_gate" in p:
            hs = _act(xf @ p["shared_gate"], cfg.act) * hs
        else:
            hs = _act(hs, cfg.act)
        out = out + hs @ p["shared_down"]

    return out.reshape(b, s, d), aux


def moe_layer_grouped(p, x, cfg):
    """§Perf (B2): group-local routing.

    Tokens are split into ``moe_groups`` groups aligned with the
    data-parallel axis; ranking / capacity / dispatch happen *inside* each
    group (a batched dimension sharded over ``data``), so the global
    argsort, rank scatter and gather collectives of the flat path
    disappear.  The expert buffers carry the group axis:
    (G→data, E→model, C, D) — the expert einsum is fully sharded with no
    resharding, and only the combine-side gather crosses the model axis
    (the all-to-all equivalent).  Capacity is per group:
    C_loc = cf·n_loc·k/E (same expected load, stricter tail — the usual
    EP trade-off).
    """
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    e, k = m.num_experts, m.top_k
    g = cfg.moe_groups
    assert n % g == 0, (n, g)
    nl = n // g
    hints = cfg.moe_shard_hints
    xg = _hint(x.reshape(g, nl, d), ("data", None, None), hints)

    with jax.named_scope("router"):
        router_logits = xg.astype(jnp.float32) @ p["router"]      # (G,NL,E)
        probs = jax.nn.softmax(router_logits, axis=-1)
        top_vals, top_ids = _top_k(probs, k)                      # (G,NL,k)
        top_vals = top_vals / jnp.clip(
            jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=(0, 1))
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_ids, e,
                                             dtype=jnp.float32),
                              axis=2), axis=(0, 1))
        aux = e * jnp.sum(me * ce) * m.router_aux_weight

    cap = int(max(k, round(m.capacity_factor * nl * k / e)))

    def rank_group(ids):
        """ids: (NL,k) — group-local capacity ranking -> (slot, keep)."""
        flat_ids = ids.reshape(-1)
        sort_idx = jnp.argsort(flat_ids)
        counts = jnp.bincount(flat_ids, length=e)
        starts = jnp.cumsum(counts) - counts
        ranks_sorted = jnp.arange(nl * k) - starts[flat_ids[sort_idx]]
        ranks = jnp.zeros_like(ranks_sorted).at[sort_idx].set(ranks_sorted)
        keep = ranks < cap
        slot = jnp.where(keep, flat_ids * cap + ranks, e * cap)
        return slot, keep

    def build_buf(xl, slot_g, keep_g):
        token_of = jnp.repeat(jnp.arange(nl), k)
        buf = jnp.zeros((e * cap + 1, d), xl.dtype)
        buf = buf.at[slot_g].set(xl[token_of], mode="drop")
        return buf[:-1].reshape(e, cap, d)

    with jax.named_scope("dispatch"):
        slot, keep = jax.vmap(rank_group)(top_ids)
        if cfg.moe_combine_shardmap:
            # per model rank, build ONLY the local experts' buffers — the
            # forward dispatch needs no collective at all (§Perf B6)
            expert_in = _dispatch_shardmap(xg, slot, keep, nl=nl, e=e,
                                           cap=cap, d=d, k=k)
        else:
            expert_in = jax.vmap(build_buf)(xg, slot, keep)
        expert_in = _hint(expert_in, ("data", "model", None, None), hints)

    with jax.named_scope("expert_ffn"):
        h = jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"])
        if "w_gate" in p:
            h = _act(jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"]),
                     cfg.act) * h
        else:
            h = _act(h, cfg.act)
        h = _hint(h, ("data", "model", None, None), hints)
        expert_out = _hint(jnp.einsum("gecf,efd->gecd", h, p["w_down"]),
                           ("data", "model", None, None), hints)

    def combine_group(outs, slot_g, keep_g, vals):
        # scatter-add combine: weighted contributions accumulate straight
        # into the (NL, D) token buffer, so the cross-shard reduction is
        # k× smaller than reducing the gathered (NL·k, D) tensor (§Perf B3)
        flat = outs.reshape(e * cap, d)
        contrib = flat[jnp.minimum(slot_g, e * cap - 1)] * \
            vals.reshape(-1)[:, None].astype(flat.dtype)     # (NL*k, D)
        token_of = jnp.repeat(jnp.arange(nl), k)
        idx = jnp.where(keep_g, token_of, nl)
        acc = jnp.zeros((nl + 1, d), jnp.float32)
        acc = acc.at[idx].add(contrib.astype(jnp.float32), mode="drop")
        return acc[:-1]

    with jax.named_scope("combine"):
        if cfg.moe_combine_shardmap:
            out = _combine_shardmap(expert_out, slot, keep, top_vals,
                                    nl=nl, e=e, cap=cap, d=d, k=k)
        else:
            out = jax.vmap(combine_group)(expert_out, slot, keep, top_vals)
        out = _hint(out.astype(x.dtype), ("data", None, None), hints)
        out = out.reshape(b, s, d)

    if "shared_up" in p:
        xf = x.reshape(n, d)
        hs = xf @ p["shared_up"]
        if "shared_gate" in p:
            hs = _act(xf @ p["shared_gate"], cfg.act) * hs
        else:
            hs = _act(hs, cfg.act)
        out = out + (hs @ p["shared_down"]).reshape(b, s, d)
    return out, aux


def _combine_shardmap(expert_out, slot, keep, vals, *, nl, e, cap, d, k):
    """§Perf (B4): explicit-collective combine.

    GSPMD's gather-based combine all-reduces the k-expanded (NL·k, D)
    tensor (B3 showed it won't exploit scatter linearity).  Under
    shard_map each model rank gathers *only its local experts'* outputs,
    scatter-adds its partial (NL, D) token buffer, and a single
    ``psum`` over 'model' finishes the job — k× less wire traffic, by
    construction.
    """
    import functools

    from jax._src.mesh import thread_resources
    from jax.sharding import PartitionSpec as P

    mesh = thread_resources.env.physical_mesh
    if mesh.empty or "model" not in mesh.axis_names or \
            e % mesh.shape["model"]:
        # fallback: no mesh (tests) or non-divisible expert count
        return _combine_gspmd(expert_out, slot, keep, vals, nl=nl, e=e,
                              cap=cap, d=d, k=k)
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    dpa = dp if len(dp) > 1 else dp[0]

    def local(eo, sl, kp, vl):
        # eo (gl, el, cap, d); sl/kp (gl, NL·k); vl (gl, NL, k)
        gl, el = eo.shape[0], eo.shape[1]
        midx = jax.lax.axis_index("model")
        base = midx * el * cap

        def one(eo_g, sl_g, kp_g, vl_g):
            loc = sl_g - base
            ok = kp_g & (loc >= 0) & (loc < el * cap)
            flat = eo_g.reshape(el * cap, d)
            contrib = flat[jnp.clip(loc, 0, el * cap - 1)] * \
                vl_g.reshape(-1)[:, None].astype(flat.dtype)
            token_of = jnp.repeat(jnp.arange(nl), k)
            idx = jnp.where(ok, token_of, nl)
            acc = jnp.zeros((nl + 1, d), jnp.float32)
            acc = acc.at[idx].add(contrib.astype(jnp.float32),
                                  mode="drop")
            return acc[:-1]

        part = jax.vmap(one)(eo, sl, kp, vl)
        return jax.lax.psum(part.astype(jnp.bfloat16), "model")

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dpa, "model", None, None), P(dpa, None), P(dpa, None),
                  P(dpa, None, None)),
        out_specs=P(dpa, None, None), check_vma=False)
    return fn(expert_out, slot, keep, vals).astype(jnp.float32)


def _dispatch_shardmap(xg, slot, keep, *, nl, e, cap, d, k):
    """§Perf (B6): collective-free forward dispatch.

    Each (data, model) rank scatters its local tokens into the buffer
    slice of its *own* experts only; the result is born sharded
    (G→data, E→model) with zero forward communication.  The backward pass
    is a single psum of the (G, NL, D) token-gradient — the mirror of the
    B4 combine.
    """
    from jax._src.mesh import thread_resources
    from jax.sharding import PartitionSpec as P

    mesh = thread_resources.env.physical_mesh
    if mesh.empty or "model" not in mesh.axis_names or \
            e % mesh.shape["model"]:
        def build(xl, sl, kp):
            token_of = jnp.repeat(jnp.arange(nl), k)
            buf = jnp.zeros((e * cap + 1, d), xl.dtype)
            buf = buf.at[sl].set(xl[token_of], mode="drop")
            return buf[:-1].reshape(e, cap, d)
        return jax.vmap(build)(xg, slot, keep)
    msize = mesh.shape["model"]
    el = e // msize
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    dpa = dp if len(dp) > 1 else dp[0]

    def local(xl, sl, kp):
        midx = jax.lax.axis_index("model")
        base = midx * el * cap

        def one(x_g, s_g, k_g):
            loc = s_g - base
            ok = k_g & (loc >= 0) & (loc < el * cap)
            idx = jnp.where(ok, loc, el * cap)
            token_of = jnp.repeat(jnp.arange(nl), k)
            buf = jnp.zeros((el * cap + 1, d), x_g.dtype)
            buf = buf.at[idx].set(x_g[token_of], mode="drop")
            return buf[:-1].reshape(el, cap, d)

        return jax.vmap(one)(xl, sl, kp)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(dpa, None, None), P(dpa, None),
                                 P(dpa, None)),
                       out_specs=P(dpa, "model", None, None),
                       check_vma=False)
    return fn(xg, slot, keep)


def _combine_gspmd(expert_out, slot, keep, vals, *, nl, e, cap, d, k):
    def combine_group(outs, slot_g, keep_g, vl):
        flat = outs.reshape(e * cap, d)
        contrib = flat[jnp.minimum(slot_g, e * cap - 1)] * \
            vl.reshape(-1)[:, None].astype(flat.dtype)
        token_of = jnp.repeat(jnp.arange(nl), k)
        idx = jnp.where(keep_g, token_of, nl)
        acc = jnp.zeros((nl + 1, d), jnp.float32)
        acc = acc.at[idx].add(contrib.astype(jnp.float32), mode="drop")
        return acc[:-1]
    return jax.vmap(combine_group)(expert_out, slot, keep, vals)


def moe_layer_dense_ref(p, x, cfg):
    """Oracle: run every expert on every token, combine by router weights.

    No capacity drops — used by tests to validate the dispatch path with a
    generous capacity factor (so nothing is dropped there either).
    """
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    router_logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_vals, top_ids = _top_k(probs, m.top_k)
    top_vals = top_vals / jnp.clip(
        jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)
    h = jnp.einsum("nd,edf->enf", xf, p["w_up"])
    if "w_gate" in p:
        h = _act(jnp.einsum("nd,edf->enf", xf, p["w_gate"]), cfg.act) * h
    else:
        h = _act(h, cfg.act)
    every = jnp.einsum("enf,efd->end", h, p["w_down"])            # (E,N,D)
    weight = jnp.zeros((xf.shape[0], m.num_experts), jnp.float32)
    weight = weight.at[jnp.arange(xf.shape[0])[:, None], top_ids].set(
        top_vals)
    out = jnp.einsum("end,ne->nd", every.astype(jnp.float32), weight)
    out = out.astype(x.dtype)
    if "shared_up" in p:
        hs = xf @ p["shared_up"]
        if "shared_gate" in p:
            hs = _act(xf @ p["shared_gate"], cfg.act) * hs
        else:
            hs = _act(hs, cfg.act)
        out = out + hs @ p["shared_down"]
    return out.reshape(b, s, d)
