"""The paper's four convolution blocks as ``ConvBlock`` subclasses.

Each class pairs the block's metadata (convolutions per step, dual
output, packing regime) with its Pallas kernel body from
``repro.kernels.conv2d``; instances are registered at import so
``get_block("conv1")`` etc. work everywhere.

The MXU dot blocks additionally override ``batched_layer`` — the
(N, H, W, C) serving hot path — with the layer-fused formulations from
``repro.blocks.base``: Conv2/Conv4 widen their im2col-plus-dot across
output channels and the batch, Conv3 keeps its operand-packing identity
(two convolutions per dot column) inside the fused dot while packing is
valid.  Conv1 is multiply-free by construction, so it inherits the
outer-vmap default.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

from repro.blocks.base import ConvBlock, fused_dot_layer, packed_dot_layer
from repro.blocks.registry import register_block
from repro.kernels import conv2d


def _partial(body, *, tile_h, w, data_bits, coeff_bits):
    return functools.partial(body, th=tile_h, w=w, data_bits=data_bits,
                             coeff_bits=coeff_bits)


@dataclass(frozen=True)
class Conv1Block(ConvBlock):
    """Multiply-free shift-add (VPU / LUT+carry-chain analogue)."""

    def kernel_body(self, *, tile_h, w, data_bits, coeff_bits):
        return _partial(conv2d.conv1_kernel, tile_h=tile_h, w=w,
                        data_bits=data_bits, coeff_bits=coeff_bits)


@dataclass(frozen=True)
class Conv2Block(ConvBlock):
    """im2col + one integer dot on the MXU (1-DSP analogue)."""

    def kernel_body(self, *, tile_h, w, data_bits, coeff_bits):
        return _partial(conv2d.conv2_kernel, tile_h=tile_h, w=w,
                        data_bits=data_bits, coeff_bits=coeff_bits)

    def batched_layer(self, x, w, *, data_bits, coeff_bits, tile_h=16):
        return fused_dot_layer(x, w, data_bits=data_bits,
                               coeff_bits=coeff_bits)


@dataclass(frozen=True)
class Conv3Block(ConvBlock):
    """Two coefficient planes packed into one operand: a single dot
    yields both convolutions while data_bits + coeff_bits ≤ 12; outside
    that regime it degrades to two dots (the discontinuity the paper's
    segmented regression models)."""

    def packed_ok(self, data_bits, coeff_bits):
        return conv2d.conv3_packed_ok(data_bits, coeff_bits)

    def kernel_body(self, *, tile_h, w, data_bits, coeff_bits):
        return _partial(conv2d.conv3_kernel, tile_h=tile_h, w=w,
                        data_bits=data_bits, coeff_bits=coeff_bits)

    def batched_layer(self, x, w, *, data_bits, coeff_bits, tile_h=16):
        if self.packed_ok(data_bits, coeff_bits):
            return packed_dot_layer(x, w, data_bits=data_bits,
                                    coeff_bits=coeff_bits)
        # outside the packing regime the kernel degrades to two dots —
        # exactly the plain fused dot
        return fused_dot_layer(x, w, data_bits=data_bits,
                               coeff_bits=coeff_bits)


@dataclass(frozen=True)
class Conv4Block(ConvBlock):
    """Two parallel dots (2-DSP analogue), two convolutions per step."""

    def kernel_body(self, *, tile_h, w, data_bits, coeff_bits):
        return _partial(conv2d.conv4_kernel, tile_h=tile_h, w=w,
                        data_bits=data_bits, coeff_bits=coeff_bits)

    def batched_layer(self, x, w, *, data_bits, coeff_bits, tile_h=16):
        return fused_dot_layer(x, w, data_bits=data_bits,
                               coeff_bits=coeff_bits)


CONV1 = register_block(Conv1Block(
    name="conv1", convs_per_step=1, dual_output=False,
    description="multiply-free shift-add (logic-only)"))
CONV2 = register_block(Conv2Block(
    name="conv2", convs_per_step=1, dual_output=False,
    description="im2col + one MXU dot (1 DSP)"))
CONV3 = register_block(Conv3Block(
    name="conv3", convs_per_step=2, dual_output=True,
    description="operand-packed dual conv (1 DSP for 2 convs when packed)"))
CONV4 = register_block(Conv4Block(
    name="conv4", convs_per_step=2, dual_output=True,
    description="two parallel MXU dots (2 DSPs)"))
