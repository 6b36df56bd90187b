"""Device idle share of a closed-loop window: 1 - busy / window, where busy
is the union of the intervals in which an operation ran on the device."""


def read(ctx):
    trace = ctx["trace"]
    if ctx["mix"]["loop"] != "closed" or trace.window_s <= 0:
        return None
    return 1.0 - trace.busy_s / trace.window_s
