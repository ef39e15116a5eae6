"""The device's idle share of the traced window, in percent: 1 less the
union of the device-op intervals over the window, averaged over the
chips used."""


def read(params, run):
    red = run.reduced
    if red is None or not red.ops or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s() / red.window_s)
