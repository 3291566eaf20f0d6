"""Device time of the slate per pane: the finalize program (sequence
state -> decode ring) and the greedy slate program, over the slate
program's runs, in the traced slice."""
from bench.metrics_io import module_time

UNIT = "ms"
LAYER = "engine"
MOVES = "slates_per_s"
SOURCE = "device_trace"
MODULES = r"^jit__\w*@engine\.(finalize|decode_slate)$"
PER = r"^jit__\w*@engine\.decode_slate$"


def read(ctx):
    t, _ = module_time(ctx, MODULES)
    _, n = module_time(ctx, PER)
    return 1e3 * t / n if n else None
