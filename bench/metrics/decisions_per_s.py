"""decisions_per_s: decisions completed in the window over the window's
length, the engine's work between decisions included."""


def read(record):
    if "decisions" not in record or record["seconds"] <= 0:
        return None
    return record["decisions"] / record["seconds"]
