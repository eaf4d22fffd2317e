"""compiles_in_window: programs compiled or loaded from the persistent
cache inside the window, counted from JAX's backend-compile events.
Warm-up should leave none."""


def read(record):
    return record["compiles_in_window"]
