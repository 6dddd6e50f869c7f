"""Share of the traced window the runtime spent moving requests and
answers between host and device: the union of its host-side transfer
spans in the profiler trace (layout conversion and DMA dispatch of each
copy, ``chipbench.trace.TRANSFER_SPANS``) over the window.  A v5e trace
holds no device-side event for these copies."""


def read(ctx):
    s = ctx.summary
    if s.window_s <= 0 or s.transfer_s <= 0:
        return None
    return 100.0 * s.transfer_s / s.window_s
