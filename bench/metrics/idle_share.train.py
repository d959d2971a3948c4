"""Share of the traced window in which no operation ran on the device
(mean over the chips used): 1 - busy / window, in %."""


def read(r):
    if r.window_s <= 0 or not r.trace.devices:
        return None
    return 100.0 * (1.0 - r.time["busy_s"] / r.window_s)
