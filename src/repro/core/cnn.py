"""Fixed-point CNN built on the paper's convolution-block library.

This is the deployment story of the paper closed end-to-end: a small CNN
whose every 3×3 layer is executed by one of the parameterizable blocks
from the ``repro.blocks`` registry, with the block chosen *by the fitted
resource models* (the Table-5 allocator) under a per-platform budget —
exactly the "model-driven block selection" workflow of §4.2.

The hot path is ``cnn_forward``: each layer runs through
``ConvBlock.apply_batched``, which convolves all (out_ch, in_ch) planes
in ONE jitted/vmapped kernel call.  It is batch-first: ``x`` may be one
(H, W, C) image or a whole (N, H, W, C) batch — the serving path of
``repro.serve.cnn_engine`` — and stays one compiled executable per
layer either way, with optional data-parallel sharding of the batch
dimension over a device mesh (``mesh=``).  ``cnn_forward_loop`` keeps
the seed's O(out_ch·in_ch) per-plane dispatch as the benchmark baseline
and a cross-check; everything is bit-exact against ``cnn_forward_ref``.

Numerics: power-of-two fixed-point. Activations and weights are quantized
to (data_bits, coeff_bits); accumulation is exact int32; each layer
rescales by a right-shift and clamps back into the activation range
(ReLU folded into the clamp).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.blocks import BIT_RANGE, BlockLike, ConvBlock, get_block
from repro.core import allocate, synth
from repro.kernels import conv2d
from repro.kernels import ops


@dataclass(frozen=True)
class ConvLayerSpec:
    in_channels: int
    out_channels: int
    data_bits: int = 8
    coeff_bits: int = 8
    shift: int = 7                 # post-accumulation right-shift
    block: Optional[str] = None    # registry name; None → allocator decides

    def __post_init__(self):
        # validate bit widths at construction (the seed let coeff_bits < 2
        # through and ``init_cnn_float`` then raised on a negative shift
        # count deep inside the weight draw)
        lo, hi = BIT_RANGE
        for name in ("data_bits", "coeff_bits"):
            bits = getattr(self, name)
            if not lo <= bits <= hi:
                raise ValueError(
                    f"ConvLayerSpec.{name}={bits} outside the supported "
                    f"block bit range {BIT_RANGE}")
        if self.shift < 0:
            raise ValueError(f"ConvLayerSpec.shift={self.shift} must be ≥ 0")
        if self.in_channels < 1 or self.out_channels < 1:
            raise ValueError(
                f"ConvLayerSpec needs ≥ 1 channel, got "
                f"{self.in_channels}→{self.out_channels}")


@dataclass
class CNNConfig:
    layers: Tuple[ConvLayerSpec, ...]
    img_h: int = 32
    img_w: int = 128


def quickstart_cnn_config() -> CNNConfig:
    """The quickstart CNN (examples/cnn_blocks.py and the batched-vs-loop
    benchmark share this single definition)."""
    return CNNConfig(layers=(
        ConvLayerSpec(1, 8, data_bits=8, coeff_bits=6),
        ConvLayerSpec(8, 8, data_bits=8, coeff_bits=6),
        ConvLayerSpec(8, 4, data_bits=6, coeff_bits=4),
    ), img_h=32, img_w=128)


# fitted-model memo for the default sweep, keyed on the sweep's schema
# version and traced-source digest: repeated planning/serving calls
# (choose_blocks, the CNN serve engine, benchmarks) share ONE
# multi-second sweep + fit per process; a change to either key
# naturally invalidates the entry
_FITTED_MODELS: Dict[tuple, allocate.BlockModels] = {}


def fitted_block_models(rows=None) -> allocate.BlockModels:
    """``BlockModels`` for the block library.  Explicit ``rows`` are
    fitted directly (caller owns the sweep); ``rows=None`` serves the
    process-wide memoized fit of the default sweep."""
    if rows is not None:
        return allocate.BlockModels.fit(rows)
    key = synth.sweep_key()
    if key not in _FITTED_MODELS:
        _FITTED_MODELS[key] = allocate.BlockModels.fit(synth.run_sweep())
    return _FITTED_MODELS[key]


def clear_fitted_model_cache() -> None:
    """Drop the memoized default-sweep fit (tests / custom registries)."""
    _FITTED_MODELS.clear()


def choose_blocks(cfg: CNNConfig, rows=None,
                  budgets=None) -> List[ConvBlock]:
    """Model-driven block selection (paper §4.2), now a thin wrapper over
    the deployment planner (``repro.core.deploy``): each layer gets the
    block the fitted models pick under the device budget at the layer's
    spec bits.  An explicit ``ConvLayerSpec.block`` wins unconditionally,
    and — matching the seed contract — selection never fails: a network
    that overflows the device falls back to the least-demanding block
    per overflowing layer instead of raising.  The default sweep's
    fitted models are memoized (``fitted_block_models``), so repeated
    calls don't re-pay the sweep.  Use ``deploy.plan_deployment``
    directly for strict budget enforcement, precision search, and the
    full plan (demand, utilization, predicted-vs-measured validation)."""
    from repro.core import deploy
    bm = fitted_block_models(rows)
    plan = deploy.plan_deployment(cfg, bm, budgets, target=0.8,
                                  on_infeasible="fallback")
    return [get_block(a.block) for a in plan.layers]


def init_cnn_float(key, cfg: CNNConfig):
    """Per-layer float weight draws *before* coefficient quantization —
    shared by ``init_cnn`` and the deployment planner's float oracle
    (``deploy.quantization_error``), so the quantized network and its
    quantization-free twin always start from the same weights."""
    params = []
    for i, spec in enumerate(cfg.layers):
        k = jax.random.fold_in(key, i)
        w = jax.random.normal(
            k, (spec.out_channels, spec.in_channels, 3, 3), jnp.float32)
        # float power keeps the formula total over every validated width
        # (the seed's ``1 << (coeff_bits - 2)`` raised on coeff_bits < 2)
        scale = 2.0 ** (spec.coeff_bits - 2) / 3.0
        params.append(w * scale)
    return params


def init_cnn(key, cfg: CNNConfig):
    return [ops.quantize_fixed(w, spec.coeff_bits)
            for w, spec in zip(init_cnn_float(key, cfg), cfg.layers)]


def _requantize(acc, spec: ConvLayerSpec):
    """Rescale + ReLU + requantize one layer's int32 accumulator —
    (out_ch, H, W) or (N, out_ch, H, W) — back into the channels-last
    activation range."""
    lo, hi = 0, (1 << (spec.data_bits - 1)) - 1
    return jnp.moveaxis(
        jnp.clip(acc >> spec.shift, lo, hi)
        .astype(conv2d.container_dtype(spec.data_bits)), -3, -1)


def cnn_forward(params, x, cfg: CNNConfig, blocks: Sequence[BlockLike],
                *, mesh=None):
    """.. deprecated:: as a serving entry point — prefer
    ``repro.runtime.CompiledCNN`` (AOT batch-bucketed executables, plan
    construction, no per-call re-threading of cfg/params/blocks/mesh).
    The signature is kept verbatim: this remains the jit-traceable
    functional core that ``CompiledCNN`` compiles per layer, and the
    oracle-adjacent path ``deploy.validate_plan`` executes.

    x: (H, W, C_in) quantized ints, or an (N, H, W, C_in) image batch.
    Returns the last layer's (H, W, C_out) — or (N, H, W, C_out).  Each
    layer is ONE ``apply_batched`` call — all (out_ch, in_ch) planes (and
    all batch images) through the assigned block in a single jitted
    executable; dual-output blocks pair output channels, keeping the
    paper's 2-convolutions-per-step semantics.

    ``mesh``: optional device mesh for data-parallel serving — every
    layer runs on each device's share of an (N, H, W, C) batch
    (``cnn_layer``).  A single image ignores it."""
    mesh = mesh if x.ndim == 4 else None
    act = x
    for spec, w, block in zip(cfg.layers, params, blocks):
        act = cnn_layer(spec, block, mesh)(w, act)
    return act


def cnn_layer(spec: ConvLayerSpec, block: BlockLike, mesh=None):
    """One layer as ``(w, x) -> activation``: ONE ``apply_batched`` call
    plus the requantize.  With ``mesh``, each device runs the layer on
    its share of the batch (``repro.parallel.sharding.
    cnn_data_parallel``)."""
    blk = get_block(block)

    def layer(w, x):
        acc = blk.apply_batched(x, w, data_bits=spec.data_bits,
                                coeff_bits=spec.coeff_bits)
        return _requantize(acc, spec)

    if mesh is None:
        return layer

    def sharded(w, x):
        from repro.parallel.sharding import cnn_data_parallel
        return cnn_data_parallel(layer, mesh, x.shape[0])(w, x)

    return sharded


def cnn_forward_loop(params, x, cfg: CNNConfig,
                     blocks: Sequence[BlockLike]):
    """Seed-era baseline: one Python-level kernel dispatch per
    (out_ch, in_ch) plane.  Kept for the batched-vs-loop benchmark
    (benchmarks/cnn_forward_bench.py) and as a cross-check; prefer
    ``cnn_forward``."""
    act = x
    for spec, w, block in zip(cfg.layers, params, blocks):
        blk = get_block(block)
        h, wd, cin = act.shape
        acc = jnp.zeros((spec.out_channels, h, wd), jnp.int32)
        step = 2 if blk.dual_output else 1
        for oc in range(0, spec.out_channels, step):
            for ic in range(cin):
                x2d = act[:, :, ic]
                if blk.dual_output:
                    oc2 = min(oc + 1, spec.out_channels - 1)
                    w2 = jnp.stack([w[oc, ic], w[oc2, ic]])
                    y = blk.apply(x2d, w2, data_bits=spec.data_bits,
                                  coeff_bits=spec.coeff_bits)
                    acc = acc.at[oc].add(y[0])
                    if oc2 != oc:
                        acc = acc.at[oc2].add(y[1])
                else:
                    y = blk.apply(x2d, w[oc, ic],
                                  data_bits=spec.data_bits,
                                  coeff_bits=spec.coeff_bits)
                    acc = acc.at[oc].add(y)
        act = _requantize(acc, spec)
    return act


def cnn_forward_ref(params, x, cfg: CNNConfig):
    """Float-free oracle using the ref conv (exact same integer math).
    Accepts a single (H, W, C) image or an (N, H, W, C) batch — batches
    run image-by-image through the scalar oracle, so the batched hot
    path is checked against genuinely independent per-image math."""
    from repro.kernels import ref
    if x.ndim == 4:
        return jnp.stack([cnn_forward_ref(params, xi, cfg) for xi in x])
    act = x
    for spec, w in zip(cfg.layers, params):
        h, wd, cin = act.shape
        acc = jnp.zeros((spec.out_channels, h, wd), jnp.int32)
        for oc in range(spec.out_channels):
            for ic in range(cin):
                acc = acc.at[oc].add(
                    ref.conv2d_3x3_ref(act[:, :, ic], w[oc, ic]))
        act = _requantize(acc, spec)
    return act
