"""Operations and bytes a layer needs, computed from its shapes at the
precision its configuration states — never what an implementation
happens to do.  A kernel rewrite then moves a roofline share, not the
yardstick.

Conventions: a multiply-add counts two operations; every value moves
once at ``bits / 8`` bytes (activations at the layer's ``data_bits``,
weights at its ``coeff_bits``, a float32 router at 32 bits).
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Work:
    ops: float       # operations (a multiply-add is two)
    bytes: float     # bytes that must cross HBM at least once

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def least_time(self, ops_per_s: float, bytes_per_s: float) -> float:
        """The roofline: the larger of compute time and memory time."""
        return max(self.ops / ops_per_s, self.bytes / bytes_per_s)


def conv3x3_layer(n: int, h: int, w: int, in_channels: int,
                  out_channels: int, data_bits: int, coeff_bits: int
                  ) -> Work:
    """One stride-1 'same' 3×3 convolution layer over ``n`` images:
    ``2·H·W·oc·ic·9`` operations an image; the input and output maps at
    ``data_bits``, the weights once at ``coeff_bits``."""
    ops = 2.0 * n * h * w * out_channels * in_channels * 9
    act = n * h * w * (in_channels + out_channels) * data_bits / 8
    weights = out_channels * in_channels * 9 * coeff_bits / 8
    return Work(ops, act + weights)


def moe_layer(tokens: int, d_model: int, num_experts: int, top_k: int,
              d_ff_expert: int, data_bits: int, coeff_bits: int,
              gated: bool = True, router_bits: int = 32) -> Work:
    """One routed MoE layer over ``tokens`` tokens: per token the router
    (``2·d·E``) and ``top_k`` expert FFNs of ``3`` (gated) or ``2``
    matrices (``2·d·d_ff`` each).  Bytes: every expert's weights once
    at ``coeff_bits``, the router at ``router_bits``, the tokens in and
    out at ``data_bits``."""
    mats = 3 if gated else 2
    ops = tokens * (2.0 * d_model * num_experts
                    + top_k * mats * 2.0 * d_model * d_ff_expert)
    weights = (num_experts * mats * d_model * d_ff_expert * coeff_bits / 8
               + d_model * num_experts * router_bits / 8)
    act = 2 * tokens * d_model * data_bits / 8
    return Work(ops, weights + act)
