"""engine_ms_per_decision: wall time of the window spent outside
`decide` (the engine's event loop, its pool replays and replay set-up),
per decision, in milliseconds, from the timing policy's clock."""


def read(record):
    n = record.get("decisions")
    if not n:
        return None
    return (record["seconds"] - sum(record["decide_s"])) / n * 1e3
