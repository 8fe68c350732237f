"""device.idle_share: the share of the traced window in which no operation
ran on the chip, 1 - (union of the device's op intervals) / window."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
