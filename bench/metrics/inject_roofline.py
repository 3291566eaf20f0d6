"""Share of the inject program's roofline: the least time the chip could
take for one call at the served shapes (max of operations over peak and
bytes over bandwidth: the configuration's ``inject`` work count, the
shared ``work.least_time``) over the measured time per run."""
from bench import work
from bench.metrics_io import module_time

UNIT = "%"
LAYER = "engine programs"
MOVES = "slates_per_s"
SOURCE = "device_trace"
MODULES = r"^jit__\w*@engine\.inject$"


def read(ctx):
    t, n = module_time(ctx, MODULES)
    if not n or t <= 0:
        return None
    s = ctx["conf"]["serving"]
    w = ctx["work"].inject(ctx["cfg"], s["max_batch"], s["inject_len"],
                           s["prefill_len"])
    return 100.0 * work.least_time(w, ctx["peak"]) / (t / n)
