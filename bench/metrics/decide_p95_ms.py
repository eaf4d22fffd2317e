"""decide_p95_ms: the 95th percentile (nearest rank) over every decision
completed in the window of the wall time from a replan request being
handed to the policy to its plan coming back, in milliseconds."""
import math


def read(record):
    xs = sorted(record.get("decide_s") or ())
    if not xs:
        return None
    return xs[math.ceil(0.95 * len(xs)) - 1] * 1e3
