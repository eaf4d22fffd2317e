"""device_ms_per_decision: the device's busy time in the traced window
(the union of its operations' intervals) per decision, in
milliseconds."""


def read(record):
    t = record.get("trace") or {}
    n = record.get("decisions")
    if not n or not t.get("busy_s"):
        return None
    return t["busy_s"] / n * 1e3
