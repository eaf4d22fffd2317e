"""reserved_rows_per_search: the mean over the traced window's device
searches of the `reserved_rows` counter on `scheduler.dispatch`: rows
of other wards' queued cloud work the search carries as reservations."""


def read(record):
    xs = [s["reserved_rows"] for s in record.get("searches") or ()
          if "reserved_rows" in s]
    if not xs:
        return None
    return sum(xs) / len(xs)
