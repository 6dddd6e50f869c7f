"""Share of the traced span the event loop spent in the gateway's own
work: the ``gateway.submit``, ``gateway.form_batch``, ``gateway.stack``
and ``gateway.resolve`` spans (none nests in another), summed, over the
span's host-clock length."""

from chipbench import spans

LOOP_SPANS = ("gateway.submit", "gateway.form_batch", "gateway.stack",
              "gateway.resolve")


def read(ctx):
    g = spans.gateway(ctx)
    if g is None or ctx.span_s <= 0:
        return None
    return 100.0 * spans.seconds(g["spans"], *LOOP_SPANS) / ctx.span_s
