"""engine_requests_ms: the `engine.requests` spans' total time in the
traced window per decision, in milliseconds: gathering every ward's
unstarted cloud commitments and building the replan requests."""


def read(record):
    req = (record.get("spans") or {}).get("engine.requests")
    n = record.get("decisions")
    if not req or not n:
        return None
    return req["total_s"] / n * 1e3
