"""Idle share of the device over the traced window: 1 - (union of device
operation intervals / window), in percent."""


def read(r):
    t = r["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
