"""pass_search_ms: the mean over the traced window's `pass`-regime
device searches of the wall time from the `scheduler.dispatch` start to
the end of its `scheduler.fetch`, in milliseconds: packed buffer in,
the width-1 movable-slot passes, the result back."""


def read(record):
    xs = [s["search_s"] for s in record.get("searches") or ()
          if s.get("regime") == "pass"]
    if not xs:
        return None
    return sum(xs) / len(xs) * 1e3
