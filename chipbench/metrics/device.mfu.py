"""The whole step's share of the chip's peak: the model operations of
the requests answered in the traced span (at the configuration's stated
bits, counted from shapes by ``chipbench.work``) over the span's length
times the chips times the peak for those bits (``chipbench.peaks``)."""

from chipbench import peaks


def read(ctx):
    if ctx.answers == 0 or ctx.span_s <= 0:
        return None
    peak = peaks.ops_peak(ctx.device_kind, ctx.ops_bits)
    return 100.0 * ctx.answers * ctx.request_ops / (
        ctx.span_s * ctx.chips * peak)
