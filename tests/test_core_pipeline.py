"""End-to-end paper pipeline: sweep → correlate → fit → allocate."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import allocate, correlate, polyfit, synth
from repro.kernels import conv2d


@pytest.fixture(scope="module")
def rows():
    return synth.run_sweep()   # cached JSON after the first benchmark run


def test_sweep_coverage(rows):
    assert len(rows) == 4 * 14 * 14
    blocks = {r["block"] for r in rows}
    assert blocks == {"conv1", "conv2", "conv3", "conv4"}


def test_conv1_has_no_mxu(rows):
    """Table 2: Conv1 uses no DSP (MXU) at all."""
    assert all(r["mxu_cost"] == 0 for r in rows if r["block"] == "conv1")
    assert all(r["mxu_cost"] > 0 for r in rows if r["block"] == "conv2")


def test_conv1_vpu_monotone_in_coeff_bits(rows):
    """Shift-add unroll: op count strictly increases with coeff bits."""
    for d in (3, 8, 16):
        ys = [r["vpu_ops"] for r in sorted(
            (r for r in rows if r["block"] == "conv1"
             and r["data_bits"] == d), key=lambda r: r["coeff_bits"])]
        assert all(a < b for a, b in zip(ys, ys[1:]))


def test_conv3_packed_regime(rows):
    """Packing happens exactly when data+coeff ≤ 12 (paper's ≤8-bit DSP
    constraint, TPU accumulator budget)."""
    for r in rows:
        if r["block"] != "conv3":
            continue
        assert bool(r["packed"]) == (r["data_bits"] + r["coeff_bits"] <= 12)


def test_conv3_packed_halves_dots(rows):
    """In the packed regime one dot column produces two convolutions:
    conv3 runs one packed column where conv4 runs one per plane, so per
    int8 MXU pass it does half conv4's dot work.  The packed column is
    wider than 8 bits, so it takes one pass per int8 limb — 3 at d4/c4,
    where conv4's 4-bit operands take one: 1.5x conv4's MXU flops."""
    packed = next(r for r in rows if r["block"] == "conv3"
                  and r["data_bits"] == 4 and r["coeff_bits"] == 4)
    conv4 = next(r for r in rows if r["block"] == "conv4"
                 and r["data_bits"] == 4 and r["coeff_bits"] == 4)
    col = jnp.zeros((1, 9), jnp.int32)
    passes = len(conv2d._limbs(col, conv2d.packed_operand_bits(4, 4)))
    assert passes == 3
    assert packed["mxu_flops"] / passes == pytest.approx(
        conv4["mxu_flops"] / 2, rel=0.01)


def test_all_models_clear_gate(rows):
    for block in ("conv1", "conv2", "conv3", "conv4"):
        d, c, ys = synth.sweep_arrays(rows, block)
        for res in synth.RESOURCES:
            if np.std(ys[res]) < 1e-12:
                continue
            m = polyfit.fit_auto(d, c, ys[res], block=block)
            met = polyfit.error_metrics(ys[res], m.predict(d, c))
            assert met["r2"] >= 0.9, (block, res, met)


def test_correlations_bounded(rows):
    for block in ("conv1", "conv2", "conv3", "conv4"):
        table = correlate.correlation_table(rows, block)
        for res, entry in table.items():
            for k, v in entry.items():
                assert -1.0001 <= v <= 1.0001


def test_allocation_respects_budgets(rows):
    bm = allocate.BlockModels.fit(rows)
    alloc = allocate.allocate(bm, data_bits=8, coeff_bits=8, target=0.8)
    assert alloc.total_convs > 0
    for r, pct in alloc.usage_pct.items():
        assert pct <= 80.0 + 1e-6, (r, pct)
    # at least one resource should be nearly saturated
    assert max(alloc.usage_pct.values()) > 60.0


def test_single_block_rows(rows):
    bm = allocate.BlockModels.fit(rows)
    for block in ("conv1", "conv2", "conv3", "conv4"):
        a = allocate.allocate(bm, data_bits=8, coeff_bits=8, target=0.8,
                              only_block=block)
        assert a.counts[block] > 0
        assert all(p <= 80.0 + 1e-6 for p in a.usage_pct.values())


def test_mixed_beats_best_single(rows):
    """The paper's headline: a model-driven mixed allocation achieves more
    total convolutions than any single-block allocation."""
    bm = allocate.BlockModels.fit(rows)
    mixed = allocate.allocate(bm, data_bits=8, coeff_bits=8, target=0.8)
    singles = [allocate.allocate(bm, data_bits=8, coeff_bits=8, target=0.8,
                                 only_block=b).total_convs
               for b in ("conv1", "conv2", "conv3", "conv4")]
    assert mixed.total_convs >= max(singles)
