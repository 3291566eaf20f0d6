"""Share of the slate's roofline: the least time the chip could take for
one pane's slate at the served shapes (the configuration's ``slate``
work count: ``slate_len - 1`` decode steps after ``prefill_len +
inject_len`` tokens; the shared ``work.least_time``) over the measured
device time of the finalize and slate programs per run of the slate
program, in the traced slice."""
from bench import work
from bench.metrics_io import module_time

UNIT = "%"
LAYER = "engine programs"
MOVES = "slates_per_s"
SOURCE = "device_trace"
MODULES = r"^jit__\w*@engine\.(finalize|decode_slate)$"
PER = r"^jit__\w*@engine\.decode_slate$"


def read(ctx):
    t, _ = module_time(ctx, MODULES)
    _, n = module_time(ctx, PER)
    if not n or t <= 0:
        return None
    s = ctx["conf"]["serving"]
    w = ctx["work"].slate(ctx["cfg"], s["max_batch"], s["slate_len"],
                          s["prefill_len"] + s["inject_len"])
    return 100.0 * work.least_time(w, ctx["peak"]) / (t / n)
