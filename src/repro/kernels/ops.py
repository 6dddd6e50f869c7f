"""Jit'd public wrappers for the kernel library + quantization helpers.

``conv_block``/``conv_block_ref`` survive only as deprecated shims over
the ``repro.blocks`` registry — use ``get_block(name).apply(...)`` /
``.reference(...)`` instead.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.kernels import conv2d, conv1d, ref


def quantize_fixed(x, bits: int, *, signed: bool = True):
    """Clamp float/int data into a ``bits``-bit signed fixed-point range and
    store it in the smallest integer container."""
    lo = -(1 << (bits - 1)) if signed else 0
    hi = (1 << (bits - 1)) - 1 if signed else (1 << bits) - 1
    q = jnp.clip(jnp.round(x), lo, hi)
    return q.astype(conv2d.container_dtype(bits))


def conv_block(block, x, w, *, data_bits, coeff_bits, tile_h=16):
    """Deprecated string-dispatch shim; use
    ``repro.blocks.get_block(block).apply(...)``."""
    warnings.warn(
        "ops.conv_block is deprecated; use "
        "repro.blocks.get_block(name).apply(...)",
        DeprecationWarning, stacklevel=2)
    from repro.blocks import get_block
    try:
        blk = get_block(block)
    except KeyError as e:       # preserve the seed contract (ValueError)
        raise ValueError(f"unknown block {block!r}") from e
    return blk.apply(x, w, data_bits=data_bits, coeff_bits=coeff_bits,
                     tile_h=tile_h)


def conv_block_ref(block, x, w, **kw):
    """Deprecated shim; use ``repro.blocks.get_block(block).reference``."""
    warnings.warn(
        "ops.conv_block_ref is deprecated; use "
        "repro.blocks.get_block(name).reference(...)",
        DeprecationWarning, stacklevel=2)
    del kw  # legacy signature compatibility
    from repro.blocks import get_block
    return get_block(block).reference(x, w)


@jax.jit
def causal_conv1d(x, w):
    return conv1d.causal_conv1d_pallas(x, w,
                                       interpret=conv2d.interpret_mode())


def causal_conv1d_ref(x, w, conv_state=None):
    return ref.causal_conv1d_ref(x, w, conv_state)
