"""Abstract ``ConvBlock``: the paper's parameterizable convolution block
as a first-class object.

The seed code represented a block as a bare string ("conv1".."conv4")
threaded through kernels, synthesis, allocation and the CNN, with every
module re-deriving block properties (dual output, packing validity,
weight shape) on its own.  ``ConvBlock`` centralizes that metadata and
behavior:

  metadata   ``name``, ``convs_per_step``, ``dual_output``,
             ``weight_shape(coeff_bits)``, ``supports(d, c)``,
             ``packed_ok(d, c)``
  execution  ``apply``       — one (H, W) plane through the Pallas kernel
             ``reference``   — pure-jnp oracle (exact integer math)
             ``apply_batched`` — ALL (out_ch, in_ch) planes of a CNN
             layer in one jitted/vmapped kernel call

``apply_batched`` is the performance half of the redesign: the seed CNN
forward dispatched one Python-level kernel call per (out_ch, in_ch)
plane — O(out_ch·in_ch) dispatches per layer.  Here the plane loop is a
nested ``jax.vmap`` over a single ``pallas_call``, so a whole layer is
one compiled executable.  Dual-output blocks keep their
2-convolutions-per-step semantics by pairing output channels (an odd
final channel is duplicated into the pair and its twin discarded), and
the int32 accumulation is exact, so results stay bit-identical to the
scalar reference.

``apply_batched`` also accepts a whole (N, H, W, in_ch) *image batch* —
the multi-image serving hot path.  The batch goes through
``batched_layer``: the default is an outer ``jax.vmap`` over the
single-image path (still one compiled executable per layer), and the
MXU dot blocks override it with a layer-fused formulation of the same
integer arithmetic (``fused_dot_layer`` / ``packed_dot_layer``) that
shares the im2col across output channels and widens the dot over the
batch — the throughput win behind ``repro.serve.cnn_engine``.  Every
path returns the exact int32 accumulator, bit-identical to the
reference.

Concrete subclasses (``repro.blocks.paper``) provide ``kernel_body``
and register themselves in the registry (``repro.blocks.registry``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import conv2d, ref

BIT_RANGE = (3, 16)     # sweep-supported data/coeff bit widths (paper §3.2)


@dataclass(frozen=True)
class ConvBlock:
    """One parameterizable 3×3 convolution block (paper §3.1).

    Frozen + hashable so instances can be jit static arguments; the
    kernel body is supplied by subclasses via ``kernel_body``.
    """

    name: str
    convs_per_step: int       # convolutions produced per grid step
    dual_output: bool         # two coefficient planes per call?
    description: str = ""

    # -- metadata -----------------------------------------------------

    def weight_shape(self, coeff_bits: int | None = None) -> Tuple[int, ...]:
        """Per-call weight operand shape (``coeff_bits`` kept for blocks
        whose operand layout depends on the coefficient width)."""
        del coeff_bits
        return (2, 3, 3) if self.dual_output else (3, 3)

    def supports(self, data_bits: int, coeff_bits: int) -> bool:
        """Whether the (data_bits, coeff_bits) design point is valid."""
        lo, hi = BIT_RANGE
        return lo <= data_bits <= hi and lo <= coeff_bits <= hi

    def packed_ok(self, data_bits: int, coeff_bits: int) -> bool:
        """Whether the block runs in its operand-packed regime at this
        design point (False for blocks that never pack)."""
        del data_bits, coeff_bits
        return False

    # -- execution ----------------------------------------------------

    def kernel_body(self, *, tile_h: int, w: int, data_bits: int,
                    coeff_bits: int):
        """Pallas kernel body for one padded row-tile (subclasses).  It
        receives the weights as one row of 9 taps per coefficient plane
        — (1, 9), or (2, 9) for dual-output blocks."""
        raise NotImplementedError

    def _validate(self, x, w, data_bits: int, coeff_bits: int,
                  tile_h: int) -> None:
        if not self.supports(data_bits, coeff_bits):
            raise ValueError(
                f"{self.name}: unsupported design point "
                f"(data_bits={data_bits}, coeff_bits={coeff_bits})")
        want = self.weight_shape(coeff_bits)
        if tuple(w.shape) != want:
            raise ValueError(
                f"{self.name}: weight shape {tuple(w.shape)} != {want}")
        if x.shape[0] % tile_h:
            raise ValueError(
                f"{self.name}: image height {x.shape[0]} not divisible by "
                f"tile_h={tile_h}")

    def apply(self, x, w, *, data_bits: int, coeff_bits: int,
              tile_h: int = 16):
        """One plane through the Pallas kernel.  x: (H, W) container int;
        w: ``weight_shape()``.  Returns int32 'same'-padded conv output —
        (H, W), or (2, H, W) for dual-output blocks."""
        self._validate(x, w, data_bits, coeff_bits, tile_h)
        return _apply_one(self, x, w, data_bits=data_bits,
                          coeff_bits=coeff_bits, tile_h=tile_h)

    def reference(self, x, w):
        """Pure-jnp oracle for ``apply`` (exact integer arithmetic)."""
        if self.dual_output:
            return jnp.stack([ref.conv2d_3x3_ref(x, w[0]),
                              ref.conv2d_3x3_ref(x, w[1])])
        return ref.conv2d_3x3_ref(x, w)

    def apply_batched(self, x, w, *, data_bits: int, coeff_bits: int,
                      tile_h: int = 16):
        """One CNN layer in a single jitted call.  x: (H, W, in_ch)
        container int, or an (N, H, W, in_ch) image batch; w: (out_ch,
        in_ch, 3, 3).  Returns the exact int32 accumulator (out_ch, H, W)
        — or (N, out_ch, H, W) — = Σ_ic conv(x[..,ic], w[oc,ic]); the
        caller applies its own rescale/activation.  Batched inputs run
        through ``batched_layer`` (one compiled executable per layer)."""
        if x.ndim not in (3, 4):
            raise ValueError(
                f"{self.name}: expected (H, W, in_ch) or (N, H, W, in_ch), "
                f"got shape {tuple(x.shape)}")
        if not self.supports(data_bits, coeff_bits):
            raise ValueError(
                f"{self.name}: unsupported design point "
                f"(data_bits={data_bits}, coeff_bits={coeff_bits})")
        if w.ndim != 4 or tuple(w.shape[2:]) != (3, 3) \
                or w.shape[1] != x.shape[-1]:
            raise ValueError(
                f"{self.name}: expected weights (out_ch, in_ch={x.shape[-1]},"
                f" 3, 3), got {tuple(w.shape)}")
        if x.shape[-3] % tile_h:
            raise ValueError(
                f"{self.name}: image height {x.shape[-3]} not divisible by "
                f"tile_h={tile_h}")
        if x.ndim == 4:
            return _apply_batched_n(self, x, w, data_bits=data_bits,
                                    coeff_bits=coeff_bits, tile_h=tile_h)
        return _apply_batched(self, x, w, data_bits=data_bits,
                              coeff_bits=coeff_bits, tile_h=tile_h)

    def batched_layer(self, x, w, *, data_bits: int, coeff_bits: int,
                      tile_h: int = 16):
        """Whole-batch layer execution: x (N, H, W, in_ch) → exact int32
        (N, out_ch, H, W).  Default: outer ``jax.vmap`` over the
        single-image plane-vmapped path — correct for any block.  The
        MXU dot blocks override this with a layer-fused dot that shares
        the im2col across output channels and the batch (bit-identical
        integer math); the multiply-free Conv1 keeps the default."""
        def one(img):
            return _apply_batched(self, img, w, data_bits=data_bits,
                                  coeff_bits=coeff_bits, tile_h=tile_h)
        return jax.vmap(one)(x)


@functools.partial(jax.jit, static_argnames=(
    "block", "data_bits", "coeff_bits", "tile_h"))
def _apply_one(block: ConvBlock, x, w, *, data_bits, coeff_bits, tile_h):
    kern = block.kernel_body(tile_h=tile_h, w=x.shape[1],
                             data_bits=data_bits, coeff_bits=coeff_bits)
    return conv2d.run_block_kernel(
        kern, x, w, n_out=2 if block.dual_output else 1, tile_h=tile_h)


@functools.partial(jax.jit, static_argnames=(
    "block", "data_bits", "coeff_bits", "tile_h"))
def _apply_batched(block: ConvBlock, x, w, *, data_bits, coeff_bits,
                   tile_h):
    h, wd, in_ch = x.shape
    out_ch = w.shape[0]
    planes = x.transpose(2, 0, 1)                      # (in_ch, H, W)

    def one(x2d, wk):
        return _apply_one(block, x2d, wk, data_bits=data_bits,
                          coeff_bits=coeff_bits, tile_h=tile_h)

    # inner vmap pairs plane ic with weight [..., ic, :, :]; outer vmap
    # broadcasts the planes across output channels (or channel pairs)
    f = jax.vmap(jax.vmap(one, in_axes=(0, 0)), in_axes=(None, 0))
    if not block.dual_output:
        y = f(planes, w)                               # (oc, ic, H, W)
        return jnp.sum(y, axis=1)                      # exact int32
    # pair output channels two per call; odd tail duplicates the last
    # channel and discards the twin — same sum as the scalar path
    if out_ch % 2:
        w = jnp.concatenate([w, w[-1:]], axis=0)
    pairs = w.shape[0] // 2
    wp = w.reshape(pairs, 2, in_ch, 3, 3).transpose(0, 2, 1, 3, 4)
    y = f(planes, wp)                                  # (p, ic, 2, H, W)
    acc = jnp.sum(y, axis=1)                           # (p, 2, H, W)
    return acc.reshape(pairs * 2, h, wd)[:out_ch]


@functools.partial(jax.jit, static_argnames=(
    "block", "data_bits", "coeff_bits", "tile_h"))
def _apply_batched_n(block: ConvBlock, x, w, *, data_bits, coeff_bits,
                     tile_h):
    return block.batched_layer(x, w, data_bits=data_bits,
                               coeff_bits=coeff_bits, tile_h=tile_h)


# ---------------------------------------------------------------------------
# layer-fused batched paths for the MXU dot blocks
#
# Same integer arithmetic as the per-plane kernels — int8/int16 products
# widen exactly into int32 and int32 accumulation is order-independent
# (mod 2^32), so both formulations are bit-identical to the reference —
# but the im2col is built once per input plane instead of once per
# (out_ch, in_ch) call, and the dot contracts over all taps × input
# channels for every output channel and image at once.
# ---------------------------------------------------------------------------

def _layer_taps(x):
    """(N, H, W, ic) → 'same'-padded tap stack (N, H, W, ic, 9)."""
    n, h, wd, ic = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return jnp.stack([xp[:, di:di + h, dj:dj + wd, :]
                      for di in range(3) for dj in range(3)], axis=-1)


def fused_dot_layer(x, w, *, data_bits: int, coeff_bits: int):
    """One integer dot for the whole layer: x (N, H, W, ic) container
    int, w (oc, ic, 3, 3) → exact int32 (N, oc, H, W).  The batched
    widening of the Conv2/Conv4 im2col-plus-dot step (operands stay in
    the kernels' dot dtype, so int8×int8 products keep the native MXU
    rate)."""
    n, h, wd, ic = x.shape
    oc = w.shape[0]
    ddt = conv2d._dot_dtype(data_bits, coeff_bits)
    pat = _layer_taps(x).astype(ddt).reshape(n, h * wd, ic * 9)
    wm = w.transpose(1, 2, 3, 0).reshape(ic * 9, oc).astype(ddt)
    y = jax.lax.dot_general(pat, wm, (((2,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    return y.reshape(n, h, wd, oc).transpose(0, 3, 1, 2)


def packed_dot_layer(x, w, *, data_bits: int, coeff_bits: int):
    """Conv3's operand packing, layer-fused: coefficient pairs share one
    int32 dot column (w_hi·2^S + w_lo), halving the dot width.  The
    S-bit field split must happen per 9-tap convolution — before the
    cross-plane sum — so the contraction runs per input channel and the
    unpacked halves accumulate afterwards (exact int32, bit-identical
    to the per-plane packed kernel)."""
    n, h, wd, ic = x.shape
    oc = w.shape[0]
    s = conv2d._pack_shift(data_bits, coeff_bits)
    if oc % 2:                      # odd tail: duplicate + discard twin
        w = jnp.concatenate([w, w[-1:]], axis=0)
    pairs = w.shape[0] // 2
    wk = w.astype(jnp.int32).reshape(pairs, 2, ic, 9)
    packed = (wk[:, 0] << s) + wk[:, 1]                # (pairs, ic, 9)
    pat = _layer_taps(x).astype(jnp.int32) \
        .transpose(0, 3, 1, 2, 4).reshape(n, ic, h * wd, 9)
    acc = jax.lax.dot_general(                         # (ic, n, HW, pairs)
        pat, packed.transpose(1, 2, 0),
        (((3,), (1,)), ((1,), (0,))),
        preferred_element_type=jnp.int32)
    hi, lo = conv2d.split_fields(acc, s)
    out = jnp.stack([jnp.sum(hi, axis=0), jnp.sum(lo, axis=0)], axis=-1)
    return out.reshape(n, h * wd, pairs * 2)[..., :oc] \
        .reshape(n, h, wd, oc).transpose(0, 3, 1, 2)
