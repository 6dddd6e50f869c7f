"""The layer executables' share of their roofline: for every dispatch
in the traced span, each layer's least time at the stated bits (the
larger of its operations over the peak and its bytes over the memory
bandwidth, ``chipbench.work``), summed, over the device time of the
layer executables in the trace."""

from chipbench import peaks


def read(ctx):
    device_s = sum(s for name, s in ctx.summary.module_s.items()
                   if name in ctx.layer_modules)
    if device_s <= 0 or not ctx.dispatches:
        return None
    ops_per_s = peaks.ops_peak(ctx.device_kind, ctx.ops_bits)
    bytes_per_s = peaks.peaks(ctx.device_kind)["hbm_bytes_per_s"]
    least = sum(count * sum(w.least_time(ops_per_s, bytes_per_s)
                            for w in ctx.dispatch_work(n))
                for n, count in ctx.dispatches.items())
    return 100.0 * least / device_s
