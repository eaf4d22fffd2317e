"""device_idle_share: the share of the traced window in which no
operation ran on the device, 100 x (1 - busy / window), in percent."""


def read(record):
    t = record.get("trace") or {}
    if not t.get("busy_s") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
