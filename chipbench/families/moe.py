"""Quantized mixture-of-experts layers served as token blocks.

The configuration holds the published expert geometry (HF config.json
key names), the served depth, the serving settings the program adds
(token block length, per-block expert capacity, the plan's bits) and
the matrix-multiply precision it states (the harness applies it to the
program; the reference computes at it).  The plan is a committed
artifact (``plan_file``), loaded through ``runtime.load_plan``.

The tokens of one block share a topic: each token is ``√ρ·c + √(1-ρ)·z``
with ``c`` drawn once per block and ``z`` per token (``ρ`` is the
configuration's ``topic_share``), so every value is N(0, 1) while the
router's choices within a block are skewed, as in a served prompt, and
the per-block capacity drops assignments.

Numerics the reference states, per layer and token block of ``S``
tokens: activations fake-quantized per token to ``data_bits``
(``round(x·s)/s``, ``s = (2^(b-1) - 1) / max|x|``); router logits in
float32 over every expert, softmax, top-k, the k weights renormalized
to sum to 1; an assignment is kept only while fewer than ``capacity =
max(k, round(cf·S·k/E))`` earlier assignments of the block (token
order, then rank within the token) went to the same expert; each kept
expert is a SiLU-gated FFN ``down(silu(gate·x) ⊙ up·x)`` of weights on
the ``coeff_bits`` grid; the block's output is ``x + Σ kept w_j·FFN``.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import work
from chipbench.families import seed_key

WEIGHT_STREAM, INPUT_STREAM = 1, 2

#: matmul precision → bfloat16 passes of the reference (``matmul``); the
#: control runs at the next entry below
PASSES = {"highest": 6, "high": 3, "default": 1}
#: a token is off where its relative error is above this (a routing
#: choice, a dropped assignment or a 4-bit rounding that went the other
#: way; sound tokens read about 1e-6)
TOKEN_OFF = 1e-3


def fake_quant(x, bits: int):
    import jax.numpy as jnp
    hi = float((1 << (bits - 1)) - 1)
    s = hi / jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-6)
    return jnp.round(x * s) / s


def matmul(spec: str, a, b, passes: int):
    """``einsum(spec, a, b)`` in float32 at the precision of ``passes``
    bfloat16 passes: 6 is full float32 (``HIGHEST``); 3 splits each
    operand into a bfloat16 high and low part and sums hi·hi, hi·lo and
    lo·hi (what ``HIGH`` does on a TPU, written out so that it computes
    the same on every backend); 1 is hi·hi alone (a TPU's ``DEFAULT``).
    The high part is rounded with
    ``reduce_precision``: XLA may drop a float32→bfloat16→float32 round
    trip as excess precision, which left the low part zero and the
    control one pass (0.03 read on the chip where three passes read
    about 1e-5)."""
    import jax
    import jax.numpy as jnp
    if passes == 6:
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if passes not in (3, 1):
        raise ValueError(f"passes={passes}: 6, 3 or 1")

    def split(v):
        hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (v - hi).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)
    pairs = ((ah, bh), (ah, bl), (al, bh))[:passes]
    return sum(jnp.einsum(spec, u, v, preferred_element_type=jnp.float32)
               for u, v in pairs)


def layer_block(p, x, *, top_k: int, capacity: int, data_bits: int,
                passes: int):
    """One residual MoE layer over one token block ``x`` (S, d), only
    the kept experts of each token computed, every matmul at
    ``passes`` (see ``matmul``)."""
    import jax
    import jax.numpy as jnp

    def mm(spec, a, b):
        return matmul(spec, a, b, passes)

    xq = fake_quant(x, data_bits)
    probs = jax.nn.softmax(mm("sd,de->se", xq, p["router"]), axis=-1)
    vals, ids = jax.lax.top_k(probs, top_k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    flat = ids.reshape(-1)
    earlier = jnp.sum(jnp.tril(flat[:, None] == flat[None, :], k=-1),
                      axis=-1)
    keep = (earlier < capacity).reshape(ids.shape)
    out = jnp.zeros_like(x)
    for j in range(top_k):
        e = ids[:, j]
        h = (jax.nn.silu(mm("sd,sdf->sf", xq, p["w_gate"][e]))
             * mm("sd,sdf->sf", xq, p["w_up"][e]))
        y = mm("sf,sfd->sd", h, p["w_down"][e])
        out = out + jnp.where(keep[:, j:j + 1], vals[:, j:j + 1] * y, 0.0)
    return x + out


class Model:
    ops_bits = 8

    def __init__(self, config: dict, seed: int, root):
        import jax
        import jax.numpy as jnp
        from repro.runtime import load_plan
        from repro.runtime.workloads import moe_plan_spec

        self.config = config
        self.seed = seed
        self.plan = load_plan(root / config["plan_file"])
        spec = moe_plan_spec(self.plan)
        self._check_plan(spec)
        self.spec = spec
        self.d = config["hidden_size"]
        self.e = config["num_experts"]
        self.k = config["num_experts_per_tok"]
        self.f = config["moe_intermediate_size"]
        self.s = config["seq_len"]
        self.bits = [(s.data_bits, s.coeff_bits) for s in spec.layers]
        self.capacity = int(max(self.k, round(
            config["capacity_factor"] * self.s * self.k / self.e)))
        ladder = list(PASSES)
        i = ladder.index(config["matmul_precision"])
        if i + 1 == len(ladder):
            raise ValueError(f"no precision below "
                             f"{config['matmul_precision']!r} for the "
                             f"control; the ladder is {ladder}")
        self.passes = PASSES[ladder[i]]
        self.control_passes = PASSES[ladder[i + 1]]

        d, e, f = self.d, self.e, self.f
        bits = self.bits

        @jax.jit
        def draw(key):
            layers = []
            for i, kl in enumerate(jax.random.split(key, len(bits))):
                ks = jax.random.split(kl, 4)
                p = {"router": jax.random.normal(ks[0], (d, e),
                                                 jnp.float32) / d ** 0.5}
                hi = float((1 << (bits[i][1] - 1)) - 1)
                for name, kw, shape, fan in (
                        ("w_up", ks[1], (e, d, f), d),
                        ("w_gate", ks[2], (e, d, f), d),
                        ("w_down", ks[3], (e, f, d), f)):
                    w = jax.random.normal(kw, shape, jnp.float32) / fan ** 0.5
                    s = hi / jnp.maximum(jnp.max(jnp.abs(w)), 1e-9)
                    p[name] = jnp.round(w * s) / s
                layers.append(p)
            return layers

        self.params = jax.block_until_ready(
            draw(seed_key(seed, WEIGHT_STREAM)))

    def _check_plan(self, spec) -> None:
        c = self.config
        want = {"hidden_size": spec.d_model, "seq_len": spec.seq_len,
                "num_hidden_layers": len(spec.layers),
                "hidden_act": spec.act}
        for s in spec.layers:
            want.update(num_experts=s.num_experts,
                        num_experts_per_tok=s.top_k,
                        moe_intermediate_size=s.d_ff_expert,
                        capacity_factor=s.capacity_factor)
            if s.n_shared_experts or not spec.mlp_gated:
                raise ValueError("the plan's layers are not SiLU-gated "
                                 "routed experts only")
        bad = {k: (v, c[k]) for k, v in want.items() if c[k] != v}
        if bad:
            raise ValueError(f"plan artifact disagrees with the "
                             f"configuration (plan, config): {bad}")

    def describe(self) -> str:
        return ", ".join(f"moe@d{d}/c{c}" for d, c in self.bits)

    def register(self, gateway) -> str:
        return gateway.register_plan(self.plan, plan_id="moe",
                                     params=self.params)

    def inputs(self, n: int) -> list:
        import jax
        import jax.numpy as jnp
        rho = self.config["topic_share"]

        @jax.jit
        def draw(key):
            kc, kz = jax.random.split(key)
            c = jax.random.normal(kc, (n, 1, self.d), jnp.float32)
            z = jax.random.normal(kz, (n, self.s, self.d), jnp.float32)
            return rho ** 0.5 * c + (1.0 - rho) ** 0.5 * z

        return list(np.asarray(draw(seed_key(self.seed, INPUT_STREAM))))

    def reference(self, xs: np.ndarray, control: bool = False, *,
                  capacity: int = None) -> np.ndarray:
        """The stack over ``xs`` (M, S, d), one block at a time on the
        device, at the stated precision — or the control: the same at
        the precision below.  ``capacity`` plants another per-block
        capacity (``faults``)."""
        import jax
        import jax.numpy as jnp
        act = jnp.asarray(xs)
        for p, (data_bits, _) in zip(self.params, self.bits):
            fn = functools.partial(
                layer_block, top_k=self.k,
                capacity=capacity or self.capacity, data_bits=data_bits,
                passes=self.control_passes if control else self.passes)
            act = jax.jit(lambda p, a: jax.lax.map(
                lambda x: fn(p, x), a))(p, act)
        return np.asarray(act)

    def faults(self, xs: np.ndarray) -> dict:
        """Faults planted in the reference put in the program's place,
        for ``probe.py``: the per-block capacity halved, and no
        capacity at all (every assignment kept)."""
        return {"capacity_half": self.reference(
                    xs, capacity=self.capacity // 2),
                "dropless": self.reference(xs, capacity=self.s * self.k)}

    @staticmethod
    def compare(got: np.ndarray, want: np.ndarray, xs: np.ndarray
                ) -> dict:
        """Per token, the relative error of what the layers add to the
        block (output minus input) against the reference's: each block's
        median, the share of tokens off (``TOKEN_OFF``), and more
        readings for ``probe.py``."""
        dg = (got - xs).astype(np.float64)
        dw = (want - xs).astype(np.float64)
        tok = (np.linalg.norm(dg - dw, axis=-1)
               / np.maximum(np.linalg.norm(dw, axis=-1), 1e-30))
        return {"block_median_rel_err_max":
                float(np.max(np.median(tok, axis=-1))),
                "tokens_off_share": float(np.mean(tok > TOKEN_OFF)),
                "token_rel_err_median": float(np.median(tok)),
                "token_rel_err_max": float(np.max(tok)),
                "rel_err_all": float(np.linalg.norm(dg - dw)
                                     / np.linalg.norm(dw))}

    def dispatch_work(self, n: int) -> list:
        return [work.moe_layer(n * self.s, self.d, self.e, self.k, self.f,
                               d_bits, c_bits)
                for d_bits, c_bits in self.bits]
