"""device_idle_share: the share of the traced window in which no op ran
on the device, in % (1 - union of op intervals / window)."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
