"""Host time of one admission on the event loop: the gateway's
``gateway.submit`` span (request validation, the adaptive bound,
``admit``; never a wait for space), its seconds over its count in the
traced span, in ms."""

from chipbench import spans


def read(ctx):
    g = spans.gateway(ctx)
    if g is None:
        return None
    count, seconds = g["spans"].get("gateway.submit", (0, 0.0))
    return 1e3 * seconds / count if count > 0 else None
