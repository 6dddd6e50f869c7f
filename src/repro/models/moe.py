"""Mixture-of-Experts with sort-based top-k dispatch under a capacity bound.

Dispatch never materializes the O(tokens × experts × capacity) one-hot
tensor of the classic einsum formulation: assignments are ranked inside
their expert via a single argsort + bincount, then scattered into a dense
(experts × capacity, d_model) buffer that feeds one batched expert matmul.
Tokens beyond capacity are dropped (standard switch-style routing); the
combine step re-weights by the router probability and sums the surviving
top-k paths.

A layer may hold a share of its experts (``experts_held`` of them from
``expert_offset``, as one device does under expert parallelism): the
router still scores every expert and ranks are computed over all of
them, but the buffer has rows only for the held experts, and what the
others would add is left out of the sum (their weights still count in
the normalization).  ``route`` also has DeepSeek-V3's sigmoid,
group-limited router with its correction bias and routed scaling.

A layer with a capacity runs ``moe_layer_grouped``, its tokens ranked
in ``moe_groups`` groups, one or many.  A layer with no capacity
(``capacity_factor`` None) drops nothing: its held assignments are
sorted by expert and run tile by tile (``_moe_layer_dropless``), as
many tiles as they fill.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import _act, dense_init, split_keys


def init_moe(key, cfg):
    m = cfg.moe
    d, fe, e = cfg.d_model, m.d_ff_expert, m.num_experts
    held = held_range(m)[1]
    dt = cfg.jnp_dtype
    ks = split_keys(key, 7)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "w_up": dense_init(ks[1], (held, d, fe), dt, fan_in=d),
        "w_down": dense_init(ks[2], (held, fe, d), dt, fan_in=fe),
    }
    if m.scoring == "sigmoid":
        # stands in for a trained model's load-balancing correction:
        # drawn, not zero, so that it moves the choice
        p["router_bias"] = 0.05 * jax.random.normal(
            jax.random.fold_in(key, 7), (e,), jnp.float32)
    if cfg.mlp_gated:
        p["w_gate"] = dense_init(ks[3], (held, d, fe), dt, fan_in=d)
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

    return {k: (v if k in ("router", "router_bias") else q(v))
            for k, v in p.items()}


def held_range(m):
    """``(offset, count)`` of the experts a layer of ``MoEConfig`` ``m``
    holds."""
    return m.expert_offset, m.experts_held or m.num_experts


#: rows of one tile of a held expert's assignments in a dropless layer
DROPLESS_TILE = 128


def route(logits, m, bias=None):
    """Top-``m.top_k`` routing over the last axis of ``logits`` (every
    expert, held or not) → ``(weights, ids, scores)``.

    ``softmax``: the probabilities, the k largest chosen.  ``sigmoid``
    (DeepSeek-V3's ``noaux_tc``): ``s = sigmoid(logits)``; the choice is
    made on ``c = s + bias`` alone: each of ``n_group`` groups scores the
    sum of its two largest ``c``, the ``topk_group`` best groups are
    kept, and the k largest ``c`` inside them are chosen; the weights
    are the chosen ``s``.  Then the weights are divided by their sum
    over all k, and they are multiplied by ``routed_scaling_factor``."""
    k = m.top_k
    if m.scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        vals, ids = jax.lax.top_k(scores, k)
    elif m.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores if bias is None else scores + bias
        if m.n_group > 1:
            e = logits.shape[-1]
            per = e // m.n_group
            best = jax.lax.top_k(
                choice.reshape(choice.shape[:-1] + (m.n_group, per)),
                min(2, per))[0].sum(axis=-1)
            _, groups = jax.lax.top_k(best, m.topk_group)
            kept = jnp.sum(jax.nn.one_hot(groups, m.n_group,
                                          dtype=jnp.int32), axis=-2) > 0
            choice = jnp.where(jnp.repeat(kept, per, axis=-1), choice,
                               -jnp.inf)
        _, ids = jax.lax.top_k(choice, k)
        vals = jnp.take_along_axis(scores, ids, axis=-1)
    else:
        raise ValueError(f"scoring={m.scoring!r}: 'softmax' or 'sigmoid'")
    vals = vals / jnp.clip(jnp.sum(vals, axis=-1, keepdims=True), 1e-9)
    if m.routed_scaling_factor != 1.0:
        vals = vals * m.routed_scaling_factor
    return vals, ids, scores


def moe_layer(p, x, cfg):
    """x: (B,S,D) -> (out (B,S,D), aux_loss scalar)."""
    out, aux, _ = moe_layer_counted(p, x, cfg)
    return out, aux


def moe_layer_counted(p, x, cfg):
    """``moe_layer`` and, per routing group, two int32 counts
    ``(G, 2)``: the assignments routed to held experts, and those kept
    under capacity.  A layer with no capacity takes the dropless path,
    every other layer the grouped one, at ``moe_groups`` groups."""
    if cfg.moe.capacity_factor is None:
        return _moe_layer_dropless(p, x, cfg)
    return moe_layer_grouped(p, x, cfg)


def _aux_loss(probs, ids, m):
    """Switch-style load-balancing loss over every leading axis."""
    e = m.num_experts
    lead = tuple(range(probs.ndim - 1))
    me = jnp.mean(probs, axis=lead)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32),
                          axis=-2), axis=lead)
    return e * jnp.sum(me * ce) * m.router_aux_weight


def _moe_layer_dropless(p, x, cfg):
    """A layer with no capacity: every assignment to a held expert is
    computed.  The held assignments are sorted by expert, each expert's
    run padded to whole tiles of ``DROPLESS_TILE`` rows; a loop per held
    expert runs as many tiles as its run fills, gathering the tiles'
    tokens, and scatter-adds the weighted outputs into the tokens.  The
    work follows the routed load; padding is under a tile an expert.
    Routing is per token, so a token's answer does not depend on the
    others; the counts are per group of ``moe_groups``, and all kept."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    k, g = m.top_k, cfg.moe_groups
    offset, held = held_range(m)
    t = DROPLESS_TILE
    xf = x.reshape(n, d)

    with jax.named_scope("router"):
        logits = xf.astype(jnp.float32) @ p["router"]             # (N,E)
        vals, ids, probs = route(logits, m, p.get("router_bias"))
        aux = _aux_loss(probs, ids, m)

    with jax.named_scope("dispatch"):
        local = ids.reshape(-1) - offset                          # (N*k,)
        mine = (local >= 0) & (local < held)
        local = jnp.where(mine, local, held)       # held: not held here
        order = jnp.argsort(local)                                # stable
        sizes = jnp.bincount(local, length=held + 1)  # last: not held
        starts = jnp.cumsum(sizes) - sizes
        rank = jnp.zeros(n * k, jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32) - starts[local[order]])
        tiles = (sizes + t - 1) // t
        first = (jnp.cumsum(tiles) - tiles) * t    # each run's first row
        # every held assignment fits, and each run adds under a tile
        rows = -(-(n * min(k, held) + held * (t - 1)) // t) * t
        at = jnp.where(mine, first[local] + rank, rows)
        token_of = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
        tok = jnp.full((rows + 1,), n, jnp.int32).at[at].set(token_of)
        w = jnp.zeros((rows + 1,), jnp.float32).at[at].set(
            vals.reshape(-1).astype(jnp.float32))
        xz = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
        mine_g = jnp.sum(mine.reshape(g, -1), axis=1, dtype=jnp.int32)
        counts = jnp.stack([mine_g, mine_g], axis=1)

    with jax.named_scope("held_ffn"):
        acc = jnp.zeros((n + 1, d), jnp.float32)
        for j in range(held):
            def tile(i, acc, j=j):
                lo = first[j] + i * t
                tt = jax.lax.dynamic_slice(tok, (lo,), (t,))
                wt = jax.lax.dynamic_slice(w, (lo,), (t,))
                xt = xz[tt]
                h = xt @ p["w_up"][j]
                if "w_gate" in p:
                    h = _act(xt @ p["w_gate"][j], cfg.act) * h
                else:
                    h = _act(h, cfg.act)
                y = (h @ p["w_down"][j]).astype(jnp.float32)
                with jax.named_scope("combine"):
                    return acc.at[tt].add(y * wt[:, None])
            acc = jax.lax.fori_loop(0, tiles[j], tile, acc)

    out = acc[:n].astype(x.dtype)
    if "shared_up" in p:
        out = out + _shared_ffn(p, xf, cfg)
    return out.reshape(b, s, d), aux, counts


def _shared_ffn(p, xf, cfg):
    """The shared experts over every token ``xf`` (N, D)."""
    with jax.named_scope("shared_ffn"):
        hs = xf @ p["shared_up"]
        if "shared_gate" in p:
            hs = _act(xf @ p["shared_gate"], cfg.act) * hs
        else:
            hs = _act(hs, cfg.act)
        return hs @ p["shared_down"]


def moe_layer_grouped(p, x, cfg):
    """A layer under a capacity; returns ``moe_layer_counted``'s triple.

    Tokens are split into ``moe_groups`` groups (one is the whole batch);
    ranking, capacity and dispatch happen inside each group, a batched
    dimension that GSPMD may shard over the data axis.  The expert
    buffers carry the group axis, (G, held, C, D), so one batched einsum
    runs every held expert of every group.  Capacity is per group:
    C_loc = cf·n_loc·k/E (same expected load, stricter tail).

    A layer that holds a share of the experts ranks every assignment as
    above and keeps those of its own experts.
    """
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    e, k = m.num_experts, m.top_k
    offset, held = held_range(m)
    g = cfg.moe_groups
    assert n % g == 0, (n, g)
    nl = n // g
    xg = x.reshape(g, nl, d)

    with jax.named_scope("router"):
        router_logits = xg.astype(jnp.float32) @ p["router"]      # (G,NL,E)
        top_vals, top_ids, probs = route(router_logits, m,
                                         p.get("router_bias"))   # (G,NL,k)
        aux = _aux_loss(probs, top_ids, m)

    cap = int(max(k, round(m.capacity_factor * nl * k / e)))
    rows = held * cap                   # buffer rows of one group

    def rank_group(ids):
        """ids: (NL,k) — group-local capacity ranking over every expert
        -> (slot in the held buffer, keep, counts)."""
        flat_ids = ids.reshape(-1)
        sort_idx = jnp.argsort(flat_ids)
        counts = jnp.bincount(flat_ids, length=e)
        starts = jnp.cumsum(counts) - counts
        ranks_sorted = jnp.arange(nl * k) - starts[flat_ids[sort_idx]]
        ranks = jnp.zeros_like(ranks_sorted).at[sort_idx].set(ranks_sorted)
        keep = ranks < cap
        if held < e:
            flat_ids = flat_ids - offset
            mine = (flat_ids >= 0) & (flat_ids < held)
            keep = keep & mine
            routed = jnp.sum(mine, dtype=jnp.int32)
        else:
            routed = jnp.int32(nl * k)
        slot = jnp.where(keep, flat_ids * cap + ranks, rows)
        return slot, keep, jnp.stack([routed,
                                      jnp.sum(keep, dtype=jnp.int32)])

    def build_buf(xl, slot_g):
        token_of = jnp.repeat(jnp.arange(nl), k)
        buf = jnp.zeros((rows + 1, d), xl.dtype)
        buf = buf.at[slot_g].set(xl[token_of], mode="drop")
        return buf[:-1].reshape(held, cap, d)

    with jax.named_scope("dispatch"):
        slot, keep, tallies = jax.vmap(rank_group)(top_ids)
        expert_in = jax.vmap(build_buf)(xg, slot)

    with jax.named_scope("held_ffn"):
        h = jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"])
        if "w_gate" in p:
            h = _act(jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"]),
                     cfg.act) * h
        else:
            h = _act(h, cfg.act)
        expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_down"])

    def combine_group(outs, slot_g, keep_g, vals):
        # scatter-add combine: weighted contributions accumulate straight
        # into the (NL, D) token buffer, so a reduction across shards is
        # k× smaller than one over the gathered (NL·k, D) tensor
        flat = outs.reshape(rows, d)
        contrib = flat[jnp.minimum(slot_g, rows - 1)] * \
            vals.reshape(-1)[:, None].astype(flat.dtype)     # (NL*k, D)
        token_of = jnp.repeat(jnp.arange(nl), k)
        idx = jnp.where(keep_g, token_of, nl)
        acc = jnp.zeros((nl + 1, d), jnp.float32)
        acc = acc.at[idx].add(contrib.astype(jnp.float32), mode="drop")
        return acc[:-1]

    with jax.named_scope("combine"):
        out = jax.vmap(combine_group)(expert_out, slot, keep, top_vals)
        out = out.astype(x.dtype).reshape(b, s, d)

    if "shared_up" in p:
        out = out + _shared_ffn(p, x.reshape(n, d), cfg).reshape(b, s, d)
    return out, aux, tallies


def moe_layer_dense_ref(p, x, cfg):
    """Oracle: run every held expert on every token, combine by router
    weights.

    No capacity drops — used by tests to validate the dispatch path with a
    generous capacity factor (so nothing is dropped there either).
    """
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    router_logits = xf.astype(jnp.float32) @ p["router"]
    top_vals, top_ids, _ = route(router_logits, m, p.get("router_bias"))
    offset, held = held_range(m)
    h = jnp.einsum("nd,edf->enf", xf, p["w_up"])
    if "w_gate" in p:
        h = _act(jnp.einsum("nd,edf->enf", xf, p["w_gate"]), cfg.act) * h
    else:
        h = _act(h, cfg.act)
    every = jnp.einsum("enf,efd->end", h, p["w_down"])            # (E,N,D)
    weight = jnp.zeros((xf.shape[0], m.num_experts), jnp.float32)
    weight = weight.at[jnp.arange(xf.shape[0])[:, None], top_ids].set(
        top_vals)[:, offset:offset + held]
    out = jnp.einsum("end,ne->nd", every.astype(jnp.float32), weight)
    out = out.astype(x.dtype)
    if "shared_up" in p:
        out = out + _shared_ffn(p, xf, cfg)
    return out.reshape(b, s, d)
