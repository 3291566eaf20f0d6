"""Share of the time the gateway held work in the traced slice (the
slice less the harness's waits for the next arrival) in which no
operation ran on the device."""
UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"


def read(ctx):
    hand = ctx["hand_s"]
    return 100.0 * (1.0 - ctx["busy_hand_s"] / hand) if hand > 0 else None
