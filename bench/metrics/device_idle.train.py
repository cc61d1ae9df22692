"""device_idle: the share of the traced window in which no operation ran on
the device (1 − busy/window, busy the union of the device's operation
intervals, averaged over the chips used), in percent."""


def read(rec):
    if not rec.get("window_s"):
        return None
    return 100.0 * (1.0 - rec["busy_s"] / rec["window_s"])
