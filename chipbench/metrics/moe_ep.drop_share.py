"""Share of the assignments routed to held experts that capacity
dropped, in %: ``1 - moe_kept_held / moe_routed_held`` over the traced
span (the program's counters)."""

from chipbench.families import moe_ep


def read(ctx):
    c = moe_ep.held_counts(ctx)
    if c is None or c["routed"] <= 0:
        return None
    return 100.0 * (1.0 - c["kept"] / c["routed"])
