"""The repro.blocks ConvBlock API: registry, metadata, batched forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.blocks import (BIT_RANGE, ConvBlock, Conv2Block, get_block,
                          list_blocks, register_block, unregister_block)
from repro.core.cnn import (CNNConfig, ConvLayerSpec, choose_blocks,
                            cnn_forward, cnn_forward_ref, init_cnn)
from repro.kernels import conv2d, ops, ref

DESIGN_POINTS = [(4, 4), (8, 8), (8, 10)]


# ---------------------------------------------------------------------------
# interpret mode: chosen once, from the backend
# ---------------------------------------------------------------------------

def _pallas_eqn(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for key in ("jaxpr", "call_jaxpr"):
            sub = eqn.params.get(key)
            if sub is not None:
                found = _pallas_eqn(getattr(sub, "jaxpr", sub))
                if found is not None:
                    return found
    return None


def _traced_kernel(name="conv1", d=8, c=8):
    blk = get_block(name)
    return jax.make_jaxpr(
        lambda x, w: blk.apply(x, w, data_bits=d, coeff_bits=c))(
        jnp.zeros((32, 128), jnp.int8),
        jnp.zeros(blk.weight_shape(c), jnp.int8)).jaxpr


def test_interpret_mode_follows_backend(monkeypatch):
    """Kernels compile on a TPU and interpret on every other backend;
    no signature carries the choice."""
    assert conv2d.interpret_mode() is (jax.default_backend() != "tpu")
    for backend, interpret in (("tpu", False), ("cpu", True),
                               ("gpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert conv2d.interpret_mode() is interpret
    monkeypatch.undo()
    # the CPU trace of the served block API carries the interpreter
    assert _pallas_eqn(_traced_kernel()).params["interpret"] is True


@pytest.mark.parametrize("name,d,c", [("conv1", 6, 4), ("conv3", 6, 4),
                                      ("conv4", 12, 10)])
def test_census_is_platform_independent(monkeypatch, name, d, c):
    """The sweep's op census reads the kernel body, which is the same
    whether the kernel is then compiled or interpreted."""
    from repro.core import hloscan

    blk = get_block(name)

    def census():
        jax.clear_caches()
        return hloscan.jaxpr_resources(
            lambda x, w: blk.apply(x, w, data_bits=d, coeff_bits=c),
            jnp.zeros((64, 128), conv2d.container_dtype(d)),
            jnp.zeros(blk.weight_shape(c), conv2d.container_dtype(c)))

    interpreted = census()
    monkeypatch.setattr(conv2d, "interpret_mode", lambda: False)
    compiled = census()
    monkeypatch.undo()
    jax.clear_caches()
    assert compiled == interpreted


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_round_trip():
    names = list_blocks()
    assert set(names) >= {"conv1", "conv2", "conv3", "conv4"}
    for name in names:
        blk = get_block(name)
        assert blk.name == name
        assert get_block(blk) is blk          # ConvBlock coerces to itself


def test_registry_unknown_and_duplicate():
    with pytest.raises(KeyError, match="conv99"):
        get_block("conv99")
    with pytest.raises(ValueError, match="already registered"):
        register_block(get_block("conv1"))


def test_register_custom_block():
    custom = Conv2Block(name="conv2_custom", convs_per_step=1,
                        dual_output=False, description="test clone")
    register_block(custom)
    try:
        assert "conv2_custom" in list_blocks()
        rng = np.random.default_rng(3)
        x = ops.quantize_fixed(
            jnp.asarray(rng.integers(-100, 100, (16, 128)), jnp.float32), 8)
        w = ops.quantize_fixed(
            jnp.asarray(rng.integers(-100, 100, (3, 3)), jnp.float32), 8)
        y = get_block("conv2_custom").apply(x, w, data_bits=8, coeff_bits=8)
        np.testing.assert_array_equal(np.asarray(y),
                                      np.asarray(custom.reference(x, w)))
    finally:
        unregister_block("conv2_custom")
    assert "conv2_custom" not in list_blocks()


# ---------------------------------------------------------------------------
# metadata
# ---------------------------------------------------------------------------

def test_block_metadata():
    for name in ("conv1", "conv2", "conv3", "conv4"):
        blk = get_block(name)
        assert blk.dual_output == (name in ("conv3", "conv4"))
        assert blk.convs_per_step == (2 if blk.dual_output else 1)
        assert blk.weight_shape(8) == ((2, 3, 3) if blk.dual_output
                                       else (3, 3))
        assert blk.supports(8, 8) and not blk.supports(2, 8)
    assert get_block("conv3").packed_ok(4, 4)
    assert not get_block("conv3").packed_ok(8, 8)


def test_apply_validates():
    blk = get_block("conv2")
    x = jnp.zeros((16, 128), jnp.int8)
    with pytest.raises(ValueError, match="unsupported design point"):
        blk.apply(x, jnp.zeros((3, 3), jnp.int8), data_bits=2, coeff_bits=8)
    with pytest.raises(ValueError, match="weight shape"):
        blk.apply(x, jnp.zeros((2, 3, 3), jnp.int8),
                  data_bits=8, coeff_bits=8)
    with pytest.raises(ValueError, match="not divisible"):
        blk.apply(jnp.zeros((17, 128), jnp.int8), jnp.zeros((3, 3), jnp.int8),
                  data_bits=8, coeff_bits=8)


# ---------------------------------------------------------------------------
# apply_batched: bit-exact vs the CNN oracle for every block
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("db,cb", DESIGN_POINTS)
@pytest.mark.parametrize("name", ["conv1", "conv2", "conv3", "conv4"])
def test_apply_batched_bit_exact(name, db, cb):
    """A two-layer CNN forced onto one block (odd + even out_channels to
    exercise the dual-output pairing tail) must equal cnn_forward_ref."""
    cfg = CNNConfig(layers=(
        ConvLayerSpec(2, 3, data_bits=db, coeff_bits=cb, block=name),
        ConvLayerSpec(3, 4, data_bits=db, coeff_bits=cb, block=name),
    ), img_h=16, img_w=128)
    params = init_cnn(jax.random.PRNGKey(42), cfg)
    rng = np.random.default_rng(db * 10 + cb)
    x = ops.quantize_fixed(
        jnp.asarray(rng.integers(0, 1 << (db - 1), (16, 128, 2)),
                    jnp.float32), db)
    blocks = [get_block(name)] * 2
    y = cnn_forward(params, x, cfg, blocks)
    yr = cnn_forward_ref(params, x, cfg)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


# BIT_RANGE edges, the Conv3 packed/unpacked boundary (d+c = 12 is the
# last packed point, 13 the first unpacked), and the narrow-accumulator
# guard: (3, 3) runs the int16 _acc_dtype path (d + c + 5 ≤ 16)
EDGE_POINTS = [
    (BIT_RANGE[0], BIT_RANGE[0]), (BIT_RANGE[0], BIT_RANGE[1]),
    (BIT_RANGE[1], BIT_RANGE[0]), (BIT_RANGE[1], BIT_RANGE[1]),
    (6, 6), (8, 4), (5, 7),        # data + coeff = 12: packed Conv3
    (7, 6), (8, 5),                # data + coeff = 13: just unpacked
]


@settings(max_examples=10, deadline=None)
@given(name=st.sampled_from(["conv1", "conv2", "conv3", "conv4"]),
       point=st.sampled_from(EDGE_POINTS),
       n=st.integers(min_value=1, max_value=3),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_apply_batched_nhwc_bit_exact_property(name, point, n, seed):
    """Property: (N, H, W, C) batches through every registry block at the
    bit-range edges — including the Conv3 packing boundary and the int16
    accumulator regime — equal the per-image scalar oracle exactly.  Odd
    out_channels exercise the dual-output pairing tail."""
    d, c = point
    blk = get_block(name)
    rng = np.random.default_rng(seed)
    ic, oc, h, w = 2, 3, 16, 64
    x = ops.quantize_fixed(
        jnp.asarray(rng.integers(-(1 << (d - 1)), 1 << (d - 1),
                                 (n, h, w, ic)), jnp.float32), d)
    wts = ops.quantize_fixed(
        jnp.asarray(rng.integers(-(1 << (c - 1)), 1 << (c - 1),
                                 (oc, ic, 3, 3)), jnp.float32), c)
    acc = blk.apply_batched(x, wts, data_bits=d, coeff_bits=c)
    assert acc.dtype == jnp.int32 and acc.shape == (n, oc, h, w)
    accr = jnp.stack([jnp.stack([
        sum(ref.conv2d_3x3_ref(x[i, :, :, j].astype(jnp.int32),
                               wts[o, j].astype(jnp.int32))
            for j in range(ic))
        for o in range(oc)]) for i in range(n)])
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(accr))


# design points at the kernels' arithmetic edges: the last narrow
# (two-per-lane) conv1 accumulator (d + c = 11), the last packed conv3
# point (d + c = 12, nine data bits → two data limbs), one-limb/two-limb
# MXU operands, and the widest point (three limbs each)
EXTREME_POINTS = [(3, 8), (6, 5), (9, 3), (8, 9), (15, 15), (16, 16)]


@pytest.mark.parametrize("d,c", EXTREME_POINTS)
@pytest.mark.parametrize("name", ["conv1", "conv2", "conv3", "conv4"])
def test_apply_extreme_values_bit_exact(name, d, c):
    """Full-scale operands (every datum and coefficient at the most
    negative or most positive code) drive every accumulator, field and
    limb to its bound: the kernels stay equal to the oracle."""
    blk = get_block(name)
    lo_d, hi_d = -(1 << (d - 1)), (1 << (d - 1)) - 1
    lo_c, hi_c = -(1 << (c - 1)), (1 << (c - 1)) - 1
    rng = np.random.default_rng(d * 17 + c)
    x = jnp.asarray(rng.choice([lo_d, hi_d], (32, 128)),
                    conv2d.container_dtype(d))
    w = jnp.asarray(rng.choice([lo_c, hi_c], blk.weight_shape(c)),
                    conv2d.container_dtype(c))
    w = w.at[..., 0, 0].set(lo_c)
    x = x.at[:8].set(lo_d)                 # a patch of all-minimum data
    got = blk.apply(x, w, data_bits=d, coeff_bits=c)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(blk.reference(x, w)))


@pytest.mark.parametrize("bits", [8, 9, 15, 16, 24])
def test_limb_split_round_trips(bits):
    """A ``bits``-bit operand split into int8 limbs recombines exactly,
    and the limb dot equals the int32 dot."""
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    v = jnp.asarray([[lo, lo + 1, -1, 0, 1, hi - 1, hi, 77]], jnp.int32)
    limbs = conv2d._limbs(v, bits)
    # 8 bits in one limb, then one 7-bit limb per 7 bits beyond
    assert len(limbs) == {8: 1, 9: 2, 15: 2, 16: 3, 24: 4}[bits]
    assert all(limb.dtype == jnp.int8 for limb in limbs)
    back = sum(limb.astype(jnp.int32) << (conv2d.LIMB_BITS * k)
               for k, limb in enumerate(limbs))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(v))
    col = jnp.asarray([[-128], [127], [1], [0], [-1], [3], [-5], [2]],
                      jnp.int32)
    want = np.asarray(v, np.int64) @ np.asarray(col, np.int64)
    got = conv2d._limb_dot(limbs, conv2d._limbs(col, 8))
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))


def test_apply_batched_raw_accumulator():
    """apply_batched returns the exact int32 Σ_ic accumulator."""
    from repro.kernels import ref
    rng = np.random.default_rng(0)
    x = ops.quantize_fixed(
        jnp.asarray(rng.integers(-100, 100, (16, 128, 3)), jnp.float32), 8)
    w = ops.quantize_fixed(
        jnp.asarray(rng.integers(-100, 100, (5, 3, 3, 3)), jnp.float32), 8)
    for name in list_blocks():
        acc = get_block(name).apply_batched(x, w, data_bits=8, coeff_bits=8)
        accr = jnp.stack([
            sum(ref.conv2d_3x3_ref(x[:, :, ic], w[oc, ic])
                for ic in range(3)) for oc in range(5)])
        assert acc.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(acc), np.asarray(accr))


# ---------------------------------------------------------------------------
# choose_blocks honors explicit overrides
# ---------------------------------------------------------------------------

def test_choose_blocks_respects_override():
    cfg = CNNConfig(layers=(
        ConvLayerSpec(1, 4, data_bits=8, coeff_bits=6, block="conv1"),
        ConvLayerSpec(4, 4, data_bits=8, coeff_bits=6),
        ConvLayerSpec(4, 2, data_bits=6, coeff_bits=6, block="conv3"),
    ), img_h=16, img_w=128)
    blocks = choose_blocks(cfg)
    assert blocks[0] is get_block("conv1")
    assert blocks[2] is get_block("conv3")
    assert isinstance(blocks[1], ConvBlock)
