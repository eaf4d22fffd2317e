"""decide_host_ms: mean wall time of a `decide` call minus the device's
busy time inside `decide` spans, in milliseconds: the policy's and the
scheduler's host work (packing, dispatch, transfers, re-scoring)."""


def read(record):
    t = record.get("trace") or {}
    n = record.get("decisions")
    if not n or "decide" not in t.get("span_s", {}):
        return None
    return (t["span_s"]["decide"] - t["busy_in"]["decide"]) / n * 1e3
