"""Device time per run of the inject program (the fresh-suffix extend),
in the traced slice."""
from bench.metrics_io import module_time

UNIT = "ms"
LAYER = "engine"
MOVES = "slates_per_s"
SOURCE = "device_trace"
MODULES = r"^jit__\w*@engine\.inject$"


def read(ctx):
    t, n = module_time(ctx, MODULES)
    return 1e3 * t / n if n else None
