"""Share of the rows the executor's buckets ran that were padding: a
dispatch of n requests runs the smallest bucket of at least n rows
(``padded_rows`` over ``rows`` in the traced span)."""

from chipbench import spans


def read(ctx):
    e = spans.executor(ctx)
    if e is None or e["rows"] <= 0:
        return None
    return 100.0 * e["padded_rows"] / e["rows"]
