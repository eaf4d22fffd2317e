"""Per-unit streams for a deployment of several hospitals' care units.

A configuration with `units` (each a `name` and a `rate_multiplier`),
`hospitals` and a `horizon` gets one ward per unit per hospital, ward
`h * len(units) + u`. Unit u's ward sends the configuration's job table
through `gen.stream` every `base_period / rate_multiplier` time units,
for the whole periods that fit the horizon, with `pairing_seed` u and
its periods in the order that seed u draws. Same-type units of every
hospital therefore send the same jobs at the same instants.

The run's seed does not reach the trace: every seed sends the same jobs
in the same order. On a pooled cloud the order of a unit's periods sets
which heavy periods of different units meet in the cloud queue, and so
how many searches carry enough other wards' work to run the `pass`
regime: 40-57% of a replay's searches over 12 seeds when the run's seed
ordered them, and the time of a decision with them.
"""
from __future__ import annotations

import math
from typing import List

from bench.traffic import gen


def unit_mix(cfg: dict, mix: dict, u: int) -> dict:
    """`gen.stream`'s parameters for unit u of the configuration."""
    period = float(mix["base_period"]) / \
        float(cfg["units"][u]["rate_multiplier"])
    return {"period": period,
            "periods": math.floor(float(cfg["horizon"]) / period),
            "pairing_seed": u}


def streams(cfg: dict, mix: dict) -> List[list]:
    """Every ward's jobs (`ward_traces` for `simulate_metro`), hospital
    by hospital, unit by unit."""
    per_unit = [gen.stream(cfg, unit_mix(cfg, mix, u), u)[0]
                for u in range(len(cfg["units"]))]
    traces = [list(t) for _ in range(int(cfg["hospitals"]))
              for t in per_unit]
    if len(traces) != int(cfg["wards"]):
        raise ValueError(f"{cfg['hospitals']} hospitals of "
                         f"{len(cfg['units'])} units make {len(traces)} "
                         f"wards; the configuration says {cfg['wards']}")
    return traces
