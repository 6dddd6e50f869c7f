"""The paper's four parameterizable convolution blocks as Pallas TPU kernels.

FPGA→TPU adaptation (DESIGN.md §2): fixed-point 3×3 convolution over an
image tile streamed through VMEM, one output row-tile per grid step ("one
convolution per cycle" → one tile per grid step).

  Conv1  multiply-free shift-add (VPU / LUT+carry-chain analogue):
         each coefficient multiply is unrolled into ``coeff_bits``
         mask-and-add passes — op count is *linear in coeff_bits*,
         zero MXU work.
  Conv2  im2col + one integer dot on the MXU (1-DSP analogue).
  Conv3  two coefficient planes packed into one integer operand
         (w_hi·2^S + w_lo): a single dot yields both convolutions,
         split arithmetically after accumulation.  Valid while both
         results fit the 32-bit accumulator guard bits
         (data_bits + coeff_bits ≤ 12 — the TPU analogue of the paper's
         ≤8-bit DSP-packing constraint; the FPGA DSP48 has a 48-bit
         accumulator where int TPU lanes have 32).  Outside that regime
         the block degrades to two dots — the discontinuity the paper's
         segmented regression models.
  Conv4  two parallel dots (2-DSP analogue), two convolutions per step.

Containers: data/coeff values quantized to ``*_bits`` live in the smallest
supported integer container (int8 ≤ 8 bits, else int16); arithmetic is
exact in int32.  The padded image is staged into VMEM in its *container*
dtype (kernels widen per-tile), so the VMEM working set scales with the
data container width — mirrored by ``synth._vmem_bytes``.

What the TPU v5e lowers, and so what the bodies are built from:

* the VPU computes in 32-bit lanes only — a narrow accumulator is two
  16-bit fields packed into one int32 lane (``_narrow_acc``), not an
  int16 vector;
* the MXU multiplies int8 (or float) operands only — an operand wider
  than 8 bits is split into int8 limbs and the partial dots are shifted
  back together in int32 (``_limb_dot``), the analogue of cascading
  DSPs for a product wider than one multiplier;
* the im2col is laid out transposed, taps on sublanes and pixels on
  lanes (``_patches``), so the dot's result is one lane-dense row.

Block selection lives in ``repro.blocks`` (the ConvBlock registry); this
module only provides the kernel bodies and the ``pallas_call`` runner.
Whether a kernel is compiled or interpreted is decided here, once, from
the backend (``interpret_mode``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PACK_SHIFT_BUDGET = 31          # int32 accumulator bits
PACKED_LIMIT = 12               # data_bits + coeff_bits ≤ 12 → packed mode
LIMB_BITS = 7                   # unsigned low limbs of a wide MXU operand


def container_dtype(bits: int):
    return jnp.int8 if bits <= 8 else jnp.int16


def conv3_packed_ok(data_bits: int, coeff_bits: int) -> bool:
    return data_bits + coeff_bits <= PACKED_LIMIT


def _pack_shift(data_bits: int, coeff_bits: int) -> int:
    # |y| <= 9 · 2^(d-1) · 2^(c-1) < 2^(d+c+2); one guard bit for sign.
    return data_bits + coeff_bits + 3


def packed_operand_bits(data_bits: int, coeff_bits: int) -> int:
    """Signed width of conv3's packed coefficient w_hi·2^S + w_lo."""
    return _pack_shift(data_bits, coeff_bits) + coeff_bits + 1


def interpret_mode() -> bool:
    """Pallas kernels compile to Mosaic on a TPU and run in the Pallas
    interpreter on every other backend."""
    return jax.default_backend() != "tpu"


def split_fields(acc, s: int):
    """``(hi, lo)`` with ``acc = hi·2^s + lo`` and ``lo`` the signed low
    ``s``-bit field — exact while both fields fit their guard bits."""
    half = jnp.int32(1 << (s - 1))
    lo = ((acc + half) & ((1 << s) - 1)) - half
    return (acc - lo) >> s, lo


# ---------------------------------------------------------------------------
# kernel bodies (operate on one padded row-tile in VMEM)
# ---------------------------------------------------------------------------

def _tile(x_ref, th):
    """Padded (th+2, w+2) rows of this grid step, widened to int32."""
    i = pl.program_id(0)
    return x_ref[pl.ds(i * th, th + 2), :].astype(jnp.int32)


def _taps(xpad, th, w):
    """9 shifted (th, w) views of the (th+2, w+2) padded tile."""
    return [xpad[di:di + th, dj:dj + w]
            for di in range(3) for dj in range(3)]


def _narrow_acc(data_bits: int, coeff_bits: int) -> bool:
    """Whether 9 taps of d-bit × c-bit products fit a 16-bit accumulator:
    d+c-1 product bits + 4 accumulation bits + sign.  Narrow accumulation
    doubles VPU lane throughput — the TPU analogue of the datapath-width
    ∝ LUT-count effect the paper measures."""
    return data_bits + coeff_bits + 5 <= 16


def conv1_kernel(x_ref, w_ref, o_ref, *, th, w, data_bits, coeff_bits):
    taps = _taps(_tile(x_ref, th), th, w)
    wk = w_ref[...].astype(jnp.int32)            # (1, 9)
    narrow = _narrow_acc(data_bits, coeff_bits) and th % 2 == 0
    if narrow:
        # two 16-bit accumulators per int32 lane: the tile's top half in
        # the high field, its bottom half in the low field.  Shifts,
        # adds and negation act on both fields at once, exactly.
        half = th // 2
        taps = [(t[:half] << 16) + t[half:] for t in taps]
    zero = jnp.zeros_like(taps[0])
    acc = zero
    for t in range(9):
        c = wk[0, t]
        mag = jnp.abs(c)
        part = zero
        for b in range(coeff_bits):          # unrolled: ops ∝ coeff_bits
            part = part + jnp.where(((mag >> b) & 1) == 1, taps[t] << b,
                                    zero)
        acc = acc + jnp.where(c < 0, -part, part)
    if narrow:
        hi, lo = split_fields(acc, 16)
        o_ref[:th // 2, :] = hi
        o_ref[th // 2:, :] = lo
    else:
        o_ref[...] = acc


def _patches(x_ref, th, w):
    """Transposed im2col of this tile: (9, th·w), one tap per row."""
    return jnp.stack(_taps(_tile(x_ref, th), th, w)).reshape(9, th * w)


def _limbs(v, bits: int):
    """int8 limbs of the int32 array ``v`` holding ``bits``-bit signed
    values, low limb first: ``v = Σ_k limb_k · 2^(7k)``.  Low limbs are
    unsigned 7-bit fields, the top limb keeps the sign."""
    out = []
    while bits > 8:
        out.append((v & ((1 << LIMB_BITS) - 1)).astype(jnp.int8))
        v = v >> LIMB_BITS
        bits -= LIMB_BITS
    out.append(v.astype(jnp.int8))
    return out


def _limb_dot(a_limbs, b_limbs):
    """Exact int32 ``a @ b`` from int8 MXU passes over the operands'
    ``_limbs``: one pass when both fit 8 bits, else one per pair of
    limbs, shifted back together (int32 wraps mod 2^32, so the sum is
    the exact product wherever that fits int32)."""
    acc = None
    for i, al in enumerate(a_limbs):
        for j, bl in enumerate(b_limbs):
            y = jax.lax.dot_general(al, bl, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.int32)
            if i + j:
                y = y << (LIMB_BITS * (i + j))
            acc = y if acc is None else acc + y
    return acc


def conv2_kernel(x_ref, w_ref, o_ref, *, th, w, data_bits, coeff_bits):
    patches = _limbs(_patches(x_ref, th, w), data_bits)
    wk = w_ref[...].astype(jnp.int32)            # (1, 9)
    y = _limb_dot(_limbs(wk, coeff_bits), patches)
    o_ref[...] = y.reshape(th, w)


def conv3_kernel(x_ref, w_ref, o_ref, *, th, w, data_bits, coeff_bits):
    patches = _limbs(_patches(x_ref, th, w), data_bits)
    wk = w_ref[...].astype(jnp.int32)            # (2, 9)
    if conv3_packed_ok(data_bits, coeff_bits):
        s = _pack_shift(data_bits, coeff_bits)
        packed = (wk[0:1] << s) + wk[1:2]        # one operand, two convs
        acc = _limb_dot(
            _limbs(packed, packed_operand_bits(data_bits, coeff_bits)),
            patches)
        hi, lo = split_fields(acc, s)
        o_ref[0] = hi.reshape(th, w)
        o_ref[1] = lo.reshape(th, w)
    else:  # fallback: packing infeasible → two dots (degenerates to Conv4)
        for j in range(2):
            y = _limb_dot(_limbs(wk[j:j + 1], coeff_bits), patches)
            o_ref[j] = y.reshape(th, w)


def conv4_kernel(x_ref, w_ref, o_ref, *, th, w, data_bits, coeff_bits):
    patches = _limbs(_patches(x_ref, th, w), data_bits)
    wk = w_ref[...].astype(jnp.int32)            # (2, 9)
    for j in range(2):                           # two parallel "DSPs"
        y = _limb_dot(_limbs(wk[j:j + 1], coeff_bits), patches)
        o_ref[j] = y.reshape(th, w)


def _dot_dtype(data_bits: int, coeff_bits: int):
    """Operand dtype of the layer-fused XLA dots (``blocks.base``): keep
    native int8 when possible — the MXU's low-precision rate is the
    analogue of fitting the DSP's 27×18 multiplier."""
    return jnp.int8 if (data_bits <= 8 and coeff_bits <= 8) else jnp.int32


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _call(kernel, xpad, wk, *, th, w, n_out):
    grid = (xpad.shape[0] - 2) // th
    out_shape = ((n_out, th * grid, w) if n_out > 1
                 else (th * grid, w))
    out_block = ((n_out, th, w) if n_out > 1 else (th, w))
    out_index = ((lambda i: (0, i, 0)) if n_out > 1
                 else (lambda i: (i, 0)))
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(xpad.shape, lambda i: (0, 0)),   # whole image VMEM
            pl.BlockSpec(wk.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec(out_block, out_index),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.int32),
        interpret=interpret_mode(),
    )(xpad, wk)


def run_block_kernel(kernel, x, wk, *, n_out: int, tile_h: int = 16):
    """Pad + run one block kernel body.  x: (H, W) container int; wk:
    (3,3) or (2,3,3), handed to the kernel as (1, 9) or (2, 9) rows of
    taps in row-major (di, dj) order.  Returns int32 conv output ((H, W) or (2, H, W)),
    zero-padded 'same' semantics.  The pad keeps the data container
    dtype — VMEM footprint scales with the container width; kernels
    widen per-tile.  Dispatch by block lives in ``repro.blocks``."""
    h, w = x.shape
    assert h % tile_h == 0, (h, tile_h)
    xpad = jnp.pad(x, ((1, 1), (1, 1)))
    # kernels take the weights as one row of 9 taps per coefficient plane
    return _call(kernel, xpad, wk.reshape(-1, 9), th=tile_h, w=w,
                 n_out=n_out)
