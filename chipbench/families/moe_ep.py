"""One chip's share of DeepSeek-V3-style MoE layers, served as token
blocks: the router spans every expert, the chip holds a few of them
and the shared expert, and computes the part of each layer that they
give.

The configuration holds the published geometry under the HF config.json
key names, with ``n_routed_experts`` the experts held here (listed in
``reduced``), ``router_experts`` the router's published width and
``expert_offset`` the first held expert's id; then the served depth and
the serving settings the program adds, as ``moe.py``'s configurations
do.  The plan is a committed artifact (``plan_file``).

Numerics the reference states, per layer and token block of ``S``
tokens: activations fake-quantized per token to ``data_bits``; router
logits in float32 over all ``E`` experts and ``s = sigmoid(logits)``;
the choice is made on ``c = s + b``, ``b`` the correction bias: each of
``n_group`` groups scores the sum of its two largest ``c``, the
``topk_group`` best groups are kept, and the ``k`` largest ``c`` inside
them are chosen; the weights are the chosen ``s`` divided by their sum
over all ``k`` (held or not) and multiplied by
``routed_scaling_factor``; no assignment is dropped (DeepSeek-V3 keeps
every token at inference, arXiv:2412.19437 §2.1.2); each held expert is
a SiLU-gated FFN on the ``coeff_bits`` grid, run densely over the
block's tokens and masked by its assignments; the shared expert runs on
every token; the block's output is ``x + shared(xq) + Σ w_j·FFN_j(xq)``
over the assignments of held experts.  The assignments of experts held
elsewhere are left out, in the program and here alike.

No weight matrix is gathered per token (at d 7168 and f 2048 one token's
three expert matrices are 176 MB).
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import work
from chipbench.families import seed_key
from chipbench.families.moe import PASSES, fake_quant, matmul
from chipbench.families.moe import Model as _QwenModel

WEIGHT_STREAM, INPUT_STREAM = 1, 2
INPUT_CHUNK = 16          # blocks drawn on the device at a time
#: a capped layer's capacity factor (the Qwen cell's), for the planted
#: fault that drops what exceeds ``max(k, round(cf·S·k/E))`` a block
CAPPED_FACTOR = 2.0


def layer_work(tokens: int, d_model: int, router_experts: int, held: int,
               top_k: int, d_ff: int, d_shared: int, data_bits: int,
               coeff_bits: int, router_bits: int = 32) -> work.Work:
    """One layer of the chip's share over ``tokens`` tokens: per token
    the router over every expert (``2·d·E``), the held experts' part of
    the ``top_k`` routed FFNs (``top_k·held/E`` of them, 3 matrices of
    ``2·d·d_ff``) and the shared FFN (3 of ``2·d·d_shared``).  Bytes:
    the held and shared weights once at ``coeff_bits``, the router at
    ``router_bits``, the tokens in and out at ``data_bits``."""
    ops = tokens * (2.0 * d_model * router_experts
                    + top_k * held / router_experts * 3 * 2.0 * d_model
                    * d_ff
                    + 3 * 2.0 * d_model * d_shared)
    weights = ((held * 3 * d_model * d_ff + 3 * d_model * d_shared)
               * coeff_bits / 8
               + d_model * router_experts * router_bits / 8)
    act = 2 * tokens * d_model * data_bits / 8
    return work.Work(ops, weights + act)


def layer_block(p, x, *, offset: int, held: int, top_k: int, n_group: int,
                topk_group: int, scale: float, data_bits: int, passes: int,
                bias: bool = True, shared: bool = True, capacity=None):
    """One residual layer of the chip's share over one token block ``x``
    (S, d), every matmul at ``passes`` (``moe.matmul``).  ``bias``,
    ``n_group`` > 1, ``scale``, ``shared`` and ``capacity`` (an
    assignment kept only while fewer than ``capacity`` earlier ones of
    the block, in token order, then rank, went to its expert) are there
    to be planted wrong (``Model.faults``)."""
    import jax
    import jax.numpy as jnp

    def mm(spec, a, b):
        return matmul(spec, a, b, passes)

    def ffn(gate, up, down):
        return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", xq, gate))
                  * mm("sd,df->sf", xq, up), down)

    xq = fake_quant(x, data_bits)
    s = jax.nn.sigmoid(mm("sd,de->se", xq, p["router"]))
    c = s + p["router_bias"] if bias else s
    n, e = s.shape
    if n_group > 1:
        per = e // n_group
        best = jax.lax.top_k(c.reshape(n, n_group, per), 2)[0].sum(-1)
        groups = jax.lax.top_k(best, topk_group)[1]
        allowed = jnp.zeros((n, n_group), bool).at[
            jnp.arange(n)[:, None], groups].set(True)
        c = jnp.where(jnp.repeat(allowed, per, axis=1), c, -jnp.inf)
    ids = jax.lax.top_k(c, top_k)[1]
    w = jnp.take_along_axis(s, ids, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True) * scale
    keep = jnp.ones(ids.shape, bool)
    if capacity is not None:
        flat = ids.reshape(-1)
        earlier = jnp.sum(jnp.tril(flat[:, None] == flat[None, :], k=-1),
                          axis=-1)
        keep = (earlier < capacity).reshape(ids.shape)
    out = x
    if shared:
        out = out + ffn(p["shared_gate"], p["shared_up"], p["shared_down"])
    for j in range(held):
        hit = (ids == offset + j) & keep        # at most one per token
        wj = jnp.sum(jnp.where(hit, w, 0.0), axis=1, keepdims=True)
        y = ffn(p["w_gate"][j], p["w_up"][j], p["w_down"][j])
        out = out + jnp.where(jnp.any(hit, axis=1, keepdims=True),
                              wj * y, 0.0)
    return out


class Model:
    ops_bits = 8
    compare = staticmethod(_QwenModel.compare)

    def __init__(self, config: dict, seed: int, root):
        import dataclasses

        import jax
        import jax.numpy as jnp
        from chipbench.harness import Refused
        from repro.runtime import load_plan
        from repro.runtime.workloads import MoELayerSpec, moe_plan_spec

        # before the plan is read: a program that cannot hold a share
        # cannot read this plan's layers either
        if "experts_held" not in {
                f.name for f in dataclasses.fields(MoELayerSpec)}:
            raise Refused("the program's MoELayerSpec has no experts_held: "
                          "it cannot hold a share of a layer's experts, so "
                          "it cannot run this configuration")
        self.config = config
        self.seed = seed
        self.plan = load_plan(root / config["plan_file"])
        spec = moe_plan_spec(self.plan)
        self._check_plan(spec)
        self.spec = spec
        c = config
        self.d = c["hidden_size"]
        self.e = c["router_experts"]
        self.held = c["n_routed_experts"]
        self.offset = c["expert_offset"]
        self.k = c["num_experts_per_tok"]
        self.f = c["moe_intermediate_size"]
        self.fs = self.f * c["n_shared_experts"]
        self.s = c["seq_len"]
        self.bits = [(s.data_bits, s.coeff_bits) for s in spec.layers]
        ladder = list(PASSES)
        i = ladder.index(c["matmul_precision"])
        if i + 1 == len(ladder):
            raise ValueError(f"no precision below "
                             f"{c['matmul_precision']!r} for the "
                             f"control; the ladder is {ladder}")
        self.passes = PASSES[ladder[i]]
        self.control_passes = PASSES[ladder[i + 1]]
        self._runs = {}

        d, e, h, f, fs = self.d, self.e, self.held, self.f, self.fs
        bias_std = c["router_bias_std"]

        # one layer at a time: a layer's draw holds its temporaries, and
        # eight layers fill most of the chip
        @functools.partial(jax.jit, static_argnums=1)
        def draw(key, coeff_bits):
            ks = jax.random.split(key, 8)
            hi = float((1 << (coeff_bits - 1)) - 1)
            p = {"router": jax.random.normal(ks[0], (d, e), jnp.float32)
                 / d ** 0.5,
                 "router_bias": bias_std * jax.random.normal(
                     ks[1], (e,), jnp.float32)}
            for name, kw, shape, fan in (
                    ("w_up", ks[2], (h, d, f), d),
                    ("w_gate", ks[3], (h, d, f), d),
                    ("w_down", ks[4], (h, f, d), f),
                    ("shared_up", ks[5], (d, fs), d),
                    ("shared_gate", ks[6], (d, fs), d),
                    ("shared_down", ks[7], (fs, d), fs)):
                w = jax.random.normal(kw, shape, jnp.float32) / fan ** 0.5
                sc = hi / jnp.maximum(jnp.max(jnp.abs(w)), 1e-9)
                p[name] = jnp.round(w * sc) / sc
            return p

        keys = jax.random.split(seed_key(seed, WEIGHT_STREAM), len(self.bits))
        self.params = [jax.block_until_ready(draw(kl, cb))
                       for kl, (_, cb) in zip(keys, self.bits)]

    def _check_plan(self, spec) -> None:
        c = self.config
        if (c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc"
                or not c["norm_topk_prob"]):
            raise ValueError("this family's reference routes as "
                             "DeepSeek-V3 does: sigmoid scores, noaux_tc, "
                             "the weights normalized over the k")
        want = {"hidden_size": spec.d_model, "seq_len": spec.seq_len,
                "num_hidden_layers": len(spec.layers),
                "hidden_act": spec.act}
        for s in spec.layers:
            want.update(router_experts=s.num_experts,
                        n_routed_experts=s.held,
                        expert_offset=s.expert_offset,
                        num_experts_per_tok=s.top_k,
                        moe_intermediate_size=s.d_ff_expert,
                        n_shared_experts=s.n_shared_experts,
                        capacity_factor=s.capacity_factor,
                        scoring_func=s.scoring, n_group=s.n_group,
                        topk_group=s.topk_group,
                        routed_scaling_factor=s.routed_scaling_factor)
            if not spec.mlp_gated:
                raise ValueError("the plan's layers are not SiLU-gated "
                                 "experts")
        bad = {k: (v, c[k]) for k, v in want.items() if c[k] != v}
        if bad:
            raise ValueError(f"plan artifact disagrees with the "
                             f"configuration (plan, config): {bad}")

    def describe(self) -> str:
        return ", ".join(f"moe_ep@d{d}/c{c}" for d, c in self.bits)

    def register(self, gateway) -> str:
        return gateway.register_plan(self.plan, plan_id="moe_ep",
                                     params=self.params)

    def inputs(self, n: int) -> list:
        """``n`` token blocks, each ``√ρ·c + √(1-ρ)·z`` as in ``moe.py``,
        drawn on the device ``INPUT_CHUNK`` at a time; each request is a
        row-major array of its own, as a client's would be."""
        import jax
        import jax.numpy as jnp
        rho = self.config["topic_share"]

        @jax.jit
        def draw(key):
            kc, kz = jax.random.split(key)
            c = jax.random.normal(kc, (INPUT_CHUNK, 1, self.d), jnp.float32)
            z = jax.random.normal(kz, (INPUT_CHUNK, self.s, self.d),
                                  jnp.float32)
            return rho ** 0.5 * c + (1.0 - rho) ** 0.5 * z

        key = seed_key(self.seed, INPUT_STREAM)
        out = []
        for i in range(-(-n // INPUT_CHUNK)):
            blocks = np.asarray(draw(jax.random.fold_in(key, i)))
            out.extend(np.array(b, order="C") for b in blocks)
        return out[:n]

    def _run(self, **settings):
        """The jitted stack of one layer over a batch of blocks, one
        block at a time, for these ``layer_block`` settings."""
        import jax
        key = tuple(sorted(settings.items()))
        if key not in self._runs:
            fn = functools.partial(layer_block, **settings)
            self._runs[key] = jax.jit(
                lambda p, a: jax.lax.map(lambda x: fn(p, x), a))
        return self._runs[key]

    def reference(self, xs: np.ndarray, control: bool = False, *,
                  capacity: int = None, bias: bool = True,
                  group_limit: bool = True, scale: float = None,
                  shared: bool = True) -> np.ndarray:
        """The stack over ``xs`` (M, S, d), one block at a time on the
        device, at the stated precision — or the control: the same at
        the precision below.  The keywords plant faults (``faults``)."""
        import jax.numpy as jnp
        c = self.config
        act = jnp.asarray(xs)
        for p, (data_bits, _) in zip(self.params, self.bits):
            act = self._run(
                offset=self.offset, held=self.held, top_k=self.k,
                n_group=c["n_group"] if group_limit else 1,
                topk_group=c["topk_group"],
                scale=(c["routed_scaling_factor"] if scale is None
                       else scale),
                data_bits=data_bits,
                passes=self.control_passes if control else self.passes,
                bias=bias, shared=shared, capacity=capacity)(p, act)
        return np.asarray(act)

    def faults(self, xs: np.ndarray) -> dict:
        """Faults planted in the reference put in the program's place,
        for ``probe.py``: the bias left out of the choice, no group
        limit, no routed scaling, no shared expert, and a capacity of
        ``CAPPED_FACTOR`` a block (drops)."""
        return {"bias_left_out": self.reference(xs, bias=False),
                "no_group_limit": self.reference(xs, group_limit=False),
                "no_scaling": self.reference(xs, scale=1.0),
                "no_shared_expert": self.reference(xs, shared=False),
                "capacity_capped": self.reference(xs, capacity=int(max(
                    self.k, round(CAPPED_FACTOR * self.s * self.k
                                  / self.e))))}

    def dispatch_work(self, n: int) -> list:
        return [layer_work(n * self.s, self.d, self.e, self.held, self.k,
                           self.f, self.fs, d_bits, c_bits)
                for d_bits, c_bits in self.bits]


def held_counts(ctx):
    """The program's held-expert counters (``moe_routed_held``,
    ``moe_kept_held``) and its bucket runs, advanced over the traced
    span and summed over the plans, as ``routed``, ``kept`` and
    ``dispatches``; None where the program keeps no such counters."""
    s0, s1 = ctx.marks["start"]["stats"], ctx.marks["stop"]["stats"]
    total = {"routed": 0, "kept": 0, "dispatches": 0}
    for pid, b in s1.items():
        a = s0.get(pid)
        if a is None or not all("moe_kept_held" in s and "moe_routed_held"
                                in s for s in (a, b)):
            return None
        total["routed"] += b["moe_routed_held"] - a["moe_routed_held"]
        total["kept"] += b["moe_kept_held"] - a["moe_kept_held"]
        total["dispatches"] += (sum(b["bucket_hits"].values())
                                - sum(a["bucket_hits"].values()))
    return total if s1 else None
