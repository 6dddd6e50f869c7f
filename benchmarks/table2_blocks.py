"""Paper Table 2: characteristics of the convolution blocks.

Reports, per registered block at the 8/8-bit design point: wall-time per
call (interpreted on the CPU, compiled on a TPU), MXU vs VPU resource
split from the op census, and convolutions per grid step — reproducing
the paper's DSP/logic trade-off rows.  Iterates the ``repro.blocks`` registry, so a
newly registered block shows up in the table automatically.
"""

from __future__ import annotations

import sys

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.blocks import get_block, list_blocks
from repro.core import synth
from repro.kernels import ops


def run():
    rng = np.random.default_rng(0)
    x = ops.quantize_fixed(
        jnp.asarray(rng.integers(-100, 100, (64, 128)), jnp.float32), 8)
    rows = synth.run_sweep()
    for name in list_blocks():
        blk = get_block(name)
        r = next((rr for rr in rows
                  if rr["block"] == name and rr["data_bits"] == 8
                  and rr["coeff_bits"] == 8), None)
        if r is None:           # block registered after the cached sweep
            print(f"table2: no sweep row for {name!r} — re-run the sweep "
                  f"with this block registered (stale cache?)",
                  file=sys.stderr)
            continue
        w = ops.quantize_fixed(
            jnp.asarray(rng.integers(-100, 100, blk.weight_shape(8)),
                        jnp.float32), 8)
        us = time_call(lambda b=blk, ww=w: b.apply(
            x, ww, data_bits=8, coeff_bits=8))
        derived = (f"mxu_cost={r['mxu_cost']:.0f};vpu_ops={r['vpu_ops']:.0f};"
                   f"convs_per_step={r['convs_per_step']:.0f};"
                   f"packed={int(r['packed'])}")
        emit(f"table2/{name}_8b", us, derived)


if __name__ == "__main__":
    run()
