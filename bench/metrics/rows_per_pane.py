"""Rows per pane in the traced slice: the gateway's requests over its
panes (``Gateway.stats``). Fuller panes spread each pane's device time over
more requests; emptier ones mean the scheduler serves arrivals as they
land."""
UNIT = "rows"
LAYER = "gateway"
SOURCE = "program_counter"


def read(ctx):
    c = ctx["slice_counters"]
    return c["requests"] / c["panes"] if c["panes"] else None
