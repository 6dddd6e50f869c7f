"""Assignments kept in the held experts' buffers per held expert and
per layer dispatch: the program's ``moe_kept_held`` counter over the
traced span, over the layer dispatches in it (bucket runs times
layers) and the experts the model holds.  At full buckets of 16
blocks of 256 tokens, top-8 over 256 experts, a held expert would be
routed 128 tokens a dispatch were the router uniform; capacity (16 a
block) caps what it keeps."""

from chipbench.families import moe_ep


def read(ctx):
    c = moe_ep.held_counts(ctx)
    held = getattr(ctx.model, "held", None)
    if c is None or not held or c["dispatches"] <= 0:
        return None
    return c["kept"] / (held * ctx.compiled.num_layers * c["dispatches"])
