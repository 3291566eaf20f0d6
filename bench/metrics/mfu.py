"""The whole step's share of the chip's peak: model operations of the
rows served in the traced slice (the configuration's ``row_flops``:
history tokens for rows that were prefilled, fresh tokens, decode
steps, one logits row per pick) over the time the gateway held work in
the slice times the peak."""
UNIT = "%"
LAYER = "whole step"
SOURCE = "device_trace"


def read(ctx):
    rows, hand = ctx["rows"], ctx["hand_s"]
    if not rows or hand <= 0:
        return None
    s = ctx["conf"]["serving"]
    f = sum(ctx["work"].row_flops(ctx["cfg"], s["prefill_len"], n,
                                  s["slate_len"], pre) for pre, n in rows)
    return 100.0 * f / (hand * ctx["peak"]["flops_per_s"])
