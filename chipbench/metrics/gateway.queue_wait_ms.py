"""Mean time a request waited in the gateway's queue: arrival to the
launch of its dispatch on the gateway's clock (``queue_wait_s``) over
the requests launched (``launched``) in the traced span, in ms."""

from chipbench import spans


def read(ctx):
    g = spans.gateway(ctx)
    if g is None or g["launched"] <= 0:
        return None
    return 1e3 * g["queue_wait_s"] / g["launched"]
