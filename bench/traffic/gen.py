"""The benchmark's one traffic generator.

Every mix under `bench/traffic/` is a JSON file of parameters; this
module turns one, with the configuration's job table and a seed, into
the inputs the program is handed: one ward's trace of jobs.

A job table (the configuration's `jobs`, one row per job under
`job_columns`) gives each job's release, weight and per-tier costs. The
stream sends the table's release times again every `period` time units
for `periods` periods. Each period deals the table's rows to its
release times by a pairing of its own, drawn from the mix's
`pairing_seed`; the run's seed only puts the periods in another order.
So every seed sends the same periods, and the same work, in another
order. Only the input type (`JobSpec`) comes from the program.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import numpy as np

CLOUD, EDGE, DEVICE = "cloud", "edge", "device"

TRAFFIC_DIR = Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    """The parameters of the mix named `name` (`bench/traffic/<name>.json`)."""
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def table_rows(cfg: dict) -> List[Dict[str, float]]:
    """The configuration's job table as one dict per job."""
    cols = cfg["job_columns"]
    return [dict(zip(cols, row)) for row in cfg["jobs"]]


def rng_of(seed: int) -> np.random.Generator:
    """The seed's stream; any whole number, however large."""
    return np.random.default_rng(int(seed) % 2**64)


def stream(cfg: dict, mix: dict, seed: int) -> List[list]:
    """One ward's jobs (`ward_traces` for `simulate_metro`), sorted by
    release: `mix["periods"]` copies of the table's release times, each
    shifted by its period, with the rows dealt to them by the period's
    pairing; `seed` orders the periods."""
    from repro.core.simulator import JobSpec
    rows = table_rows(cfg)
    releases = [float(r["release"]) for r in rows]
    period = float(mix["period"])
    pairing = rng_of(mix["pairing_seed"])
    pairings = [pairing.permutation(len(rows))
                for _ in range(int(mix["periods"]))]
    jobs = []
    for p, q in enumerate(rng_of(seed).permutation(len(pairings))):
        for release, k in zip(releases, pairings[q]):
            r = rows[int(k)]
            jobs.append(JobSpec(
                name=f"p{p}-{r['name']}", release=release + p * period,
                weight=float(r["weight"]),
                proc={CLOUD: float(r["cloud_proc"]),
                      EDGE: float(r["edge_proc"]),
                      DEVICE: float(r["device_proc"])},
                trans={CLOUD: float(r["cloud_trans"]),
                       EDGE: float(r["edge_trans"]), DEVICE: 0.0}))
    jobs.sort(key=lambda j: (j.release, j.name))
    return [jobs]
