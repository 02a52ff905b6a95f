"""Share of the traced window in which no operation ran on the device
(%): 1 - union of the device-busy intervals / the window."""


def read(ctx):
    red = ctx["trace"]
    return 100.0 * (1.0 - red.busy_s() / red.window_s) if red.window_s > 0 else None
