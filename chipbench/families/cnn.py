"""Fixed-point 3×3 convolution stacks (the paper's workload).

The configuration lists its layers (stride-1 'same' 3×3 convolutions
with ReLU) at their bits and shift.  The plan comes from the program's
planner for the configuration's device profile, at the stated bits:
a block search only, so the block choice is a speed decision.

Numerics the reference states: activations are non-negative
``data_bits`` integers, weights signed ``coeff_bits`` integers, the
accumulation exact in int32; each layer shifts the accumulator right by
``shift`` (arithmetic), clamps into ``[0, 2^(data_bits-1) - 1]`` (the
ReLU folded into the clamp) and stores the result in int8 (the family
serves up to 8 bits).
"""

from __future__ import annotations

import numpy as np

from chipbench import work
from chipbench.families import seed_key

WEIGHT_STREAM, INPUT_STREAM = 1, 2


def _check_config(config: dict) -> None:
    for i, layer in enumerate(config["layers"]):
        shape = (layer["kernel"], layer["stride"], layer["padding"],
                 layer["relu"])
        if shape != (3, 1, "same", True):
            raise ValueError(f"layer {i}: only stride-1 'same' 3×3 "
                             f"convolutions with ReLU are served, got "
                             f"{shape}")
        if max(layer["data_bits"], layer["coeff_bits"]) > 8:
            raise ValueError(f"layer {i}: values are held in int8; "
                             f"bits above 8 are not served here")
        if i and layer["in_channels"] != config["layers"][i - 1][
                "out_channels"]:
            raise ValueError(f"layer {i}: in_channels does not follow "
                             f"layer {i - 1}'s out_channels")


def conv_ref(x, w, shift: int, data_bits: int, drop_x: int = 0,
             drop_w: int = 0):
    """One layer of the plain reference over a batch.

    ``x`` (N, H, W, ic) non-negative ints, ``w`` (oc, ic, 3, 3).  The
    convolution is nine shifted int8 dots accumulated exactly in int32.
    ``drop_x``/``drop_w`` > 0 compute it on operands with that many low
    bits dropped (the lower-precision control), rescaled back."""
    import jax.numpy as jnp
    n, h, wd, _ = x.shape
    xs = (x.astype(jnp.int32) >> drop_x).astype(jnp.int8)
    ws = (w.astype(jnp.int32) >> drop_w).astype(jnp.int8)
    xp = jnp.pad(xs, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = 0
    for di in range(3):
        for dj in range(3):
            acc = acc + jnp.einsum(
                "nhwc,oc->nhwo", xp[:, di:di + h, dj:dj + wd, :],
                ws[:, :, di, dj], preferred_element_type=jnp.int32)
    acc = acc << (drop_x + drop_w)
    hi = (1 << (data_bits - 1)) - 1
    return jnp.clip(acc >> shift, 0, hi).astype(jnp.int8)


class Model:
    ops_bits = 8

    def __init__(self, config: dict, seed: int, root):
        import jax
        import jax.numpy as jnp
        from repro.core.cnn import CNNConfig, ConvLayerSpec

        _check_config(config)
        self.config = config
        self.seed = seed
        self.layers = config["layers"]
        self.h, self.w = config["img_h"], config["img_w"]
        self.cnn = CNNConfig(layers=tuple(
            ConvLayerSpec(s["in_channels"], s["out_channels"],
                          data_bits=s["data_bits"],
                          coeff_bits=s["coeff_bits"], shift=s["shift"])
            for s in self.layers), img_h=self.h, img_w=self.w)
        self.plan = self._plan(config["plan"])
        for a, s in zip(self.plan.layers, self.layers):
            if (a.data_bits, a.coeff_bits) != (s["data_bits"],
                                                s["coeff_bits"]):
                raise ValueError(f"plan layer {a.index} moved the bits "
                                 f"to d{a.data_bits}/c{a.coeff_bits}")

        layers = self.layers

        @jax.jit
        def draw(key):
            ws = []
            for i, s in enumerate(layers):
                c = s["coeff_bits"]
                g = jax.random.normal(
                    jax.random.fold_in(key, i),
                    (s["out_channels"], s["in_channels"], 3, 3),
                    jnp.float32)
                q = jnp.round(g * (2.0 ** (c - 2) / 3.0))
                ws.append(jnp.clip(q, -(1 << (c - 1)), (1 << (c - 1)) - 1)
                          .astype(jnp.int8))
            return ws

        self.params = jax.block_until_ready(
            draw(seed_key(seed, WEIGHT_STREAM)))

    def _plan(self, p: dict):
        from repro.core import allocate, cnn, deploy
        return deploy.plan_deployment(
            self.cnn, cnn.fitted_block_models(),
            allocate.get_device(p["profile"]),
            target=p["target"], on_infeasible=p["on_infeasible"])

    def describe(self) -> str:
        return ", ".join(f"{a.block}@d{a.data_bits}/c{a.coeff_bits}"
                         for a in self.plan.layers)

    def register(self, gateway) -> str:
        return gateway.register_plan(self.plan, plan_id="cnn",
                                     params=self.params)

    def inputs(self, n: int) -> list:
        import jax
        import jax.numpy as jnp
        s0 = self.layers[0]
        hi = 1 << (s0["data_bits"] - 1)
        x = jax.jit(lambda k: jax.random.randint(
            k, (n, self.h, self.w, s0["in_channels"]), 0, hi, jnp.int32)
            .astype(jnp.int8))(
                seed_key(self.seed, INPUT_STREAM))
        return list(np.asarray(x))

    def reference(self, xs: np.ndarray, control: bool = False,
                  chunk: int = 8) -> np.ndarray:
        """The stack over ``xs`` (M, H, W, ic), ``chunk`` images a call.
        The control drops each operand to 4 bits (int4 for int8)."""
        import jax
        import jax.numpy as jnp
        drops = [((s["data_bits"] - 4, s["coeff_bits"] - 4) if control
                  else (0, 0)) for s in self.layers]

        @jax.jit
        def stack(params, x):
            for s, w, (dx, dw) in zip(self.layers, params, drops):
                x = conv_ref(x, w, s["shift"], s["data_bits"], dx, dw)
            return x

        return np.concatenate([
            np.asarray(stack(self.params, jnp.asarray(xs[i:i + chunk])))
            for i in range(0, len(xs), chunk)])

    @staticmethod
    def compare(got: np.ndarray, want: np.ndarray, xs: np.ndarray
                ) -> dict:
        bad = np.any(got != want, axis=tuple(range(1, got.ndim)))
        return {"mismatched_answers": int(bad.sum()),
                "mismatched_values": int(np.sum(got != want))}

    def dispatch_work(self, n: int) -> list:
        return [work.conv3x3_layer(n, self.h, self.w, s["in_channels"],
                                   s["out_channels"], s["data_bits"],
                                   s["coeff_bits"]) for s in self.layers]
