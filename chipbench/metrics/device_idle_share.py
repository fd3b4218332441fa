"""Share of the window in which no op ran on the device: 1 - the union of
op intervals over the window, averaged over the cell's chips."""
NAME = "device_idle_share"
UNIT = "%"
LAYER = "device"


def compute(ctx):
    red = ctx["red"]
    return 100.0 * (1.0 - red.busy_s / red.window_s) if red.window_s else None
