"""Host cost of one dispatch outside the wait for the device: the hop
to the dispatch worker and back (``gateway.handoff``), bucket padding
(``executor.pad``), the copy in (``executor.h2d``), the layers'
launches (``executor.launch``) and the copy back (``executor.d2h``),
summed, over the bucket runs in the traced span, in ms."""

from chipbench import spans

HOST_SPANS = ("gateway.handoff", "executor.pad", "executor.h2d",
              "executor.launch", "executor.d2h")


def read(ctx):
    g, e = spans.gateway(ctx), spans.executor(ctx)
    if g is None or e is None or e["dispatches"] <= 0:
        return None
    host_s = spans.seconds({**g["spans"], **e["spans"]}, *HOST_SPANS)
    return 1e3 * host_s / e["dispatches"]
