"""pass_evals_per_search: the mean over the traced window's `pass`-regime
device searches of the tier evaluations each ran, from the `pass_evals`
count that `scheduler.fetch` carries (summed in the recorder's summary)
over the `regime=pass` count on `scheduler.dispatch`."""


def read(record):
    recorded = record.get("spans") or {}
    passes = (recorded.get("scheduler.dispatch") or {}).get("regime=pass")
    evals = (recorded.get("scheduler.fetch") or {}).get("pass_evals")
    if not passes or evals is None:
        return None
    return evals / passes
