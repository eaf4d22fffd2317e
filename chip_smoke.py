"""Bring-up smoke of the served placement path on one TPU chip.

    python chip_smoke.py

Drives `repro.launch.serve` once in each of its three modes, in this one
process, and checks every phase against a plain reference:

1. `serve.run`: 200 patients placed on the TPU tiers by the device
   search, then real inference by the three ICU LSTMs. "ours" must beat
   every baseline and respect the lower bound; each compiled forward must
   hold the Pallas `lstm_cell` kernel, and its logits must match a
   `lax.scan` over the jnp reference cell at highest matmul precision.
2. Fleet planning over 32 wards of 100 jobs: `serve.run_wards` in
   contention mode, then `scheduler.search_fleet` on the contention
   fleet of BENCH_scheduler.json. The batched device search's own
   objectives, in its round regime (independent plans) and its pass
   regime (a sweep against interval reservations), must equal
   `simulate` of the assignments it returns; each fleet-true plan must
   be no worse than the naive one and recover part of the contention
   gap.
3. `serve.run_metro`: the `mass_casualty_crash` pack with every replan on
   the device search, the sanitizer armed and each policy run twice; the
   two event logs must be bit-identical.

Every phase is a plain function, so the tests run them at tiny size on
the CPU. `main()` refuses any device that is not a TPU. The lines before
the last are bring-up output (device, each phase's wall time and compile
count, each check's numbers), not benchmark results. The last line is
one JSON object naming the device.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import scheduler, scheduler_jax  # noqa: E402
from repro.core.problems import metro_jobs  # noqa: E402
from repro.core.simulator import MACHINES, simulate  # noqa: E402
from repro.core.tiers import CC, ES, paper_tiers  # noqa: E402
from repro.data import icu  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.utils import spans  # noqa: E402
from repro.utils.compile_cache import enable_compilation_cache  # noqa: E402

OURS = "ours (algorithm 2)"
# Logits of the served forward vs the highest-precision reference. At
# the TPU's default precision f32 matmul operands are rounded to bf16
# (relative error 2^-9 = 2e-3), on the kernel's gates and on the head.
# On a TPU v5e the largest error over the three workloads was 2.5e-3
# (phenotype, logits up to 0.85); the limit leaves 4x that. A path that
# computed the wrong thing would miss it by orders of magnitude.
LOGITS_ATOL = 1e-2
# Device search objectives vs `simulate` (float64): the kernel
# accumulates in float32, whose relative rounding is 6e-8 per operation,
# over up to a few thousand rows. On a TPU v5e the largest relative gap
# was 1.4e-6 (pass regime, 1600 rows); the limit leaves 7x that.
OBJECTIVE_RTOL = 1e-5


def _device_searches(rec: spans.Recorder) -> int:
    """Device searches in a recording: one `scheduler.dispatch` each."""
    return len(rec.durations("scheduler.dispatch"))


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def reference_logits(params, x, cfg):
    """The ICU LSTM forward as a `lax.scan` over the jnp reference cell,
    at highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        h_seq = jnp.asarray(x)
        bsz = h_seq.shape[0]
        for layer in params["layers"]:
            i_dim = layer["wx"].shape[0]
            wx = layer["wx"].reshape(i_dim, 4 * cfg.hidden)
            wh = layer["wh"].reshape(cfg.hidden, 4 * cfg.hidden)
            b = layer["b"].reshape(4 * cfg.hidden)

            def step(carry, xt, wx=wx, wh=wh, b=b):
                h, c = ref.lstm_cell_reference(xt, *carry, wx, wh, b)
                return (h, c), h

            zeros = jnp.zeros((bsz, cfg.hidden), h_seq.dtype)
            (h, _), hs = jax.lax.scan(step, (zeros, zeros),
                                      jnp.moveaxis(h_seq, 1, 0))
            h_seq = jnp.moveaxis(hs, 0, 1)
        return h @ params["head"] + params["head_b"]


def phase_icu(patients: int = 200) -> dict:
    """serve.run on the TPU tiers with execution, then each ICU
    forward's kernel and numerics."""
    with spans.recording() as rec:
        results, lb = serve.run(patients=patients, tiers_kind="tpu",
                                execute=True, verbose=False)
    ours = results[OURS].weighted_sum
    for name, sched in results.items():
        _check(ours <= sched.weighted_sum,
               f"ours {ours} > {name} {sched.weighted_sum}")
    _check(ours >= lb, f"ours {ours} < lower bound {lb}")
    on_device = _device_searches(rec) > 0
    want_device = (patients > scheduler.JAX_SEARCH_THRESHOLD
                   and jax.default_backend() != "cpu")
    _check(on_device == want_device,
           f"device search used: {on_device}, expected {want_device}")

    on_tpu = jax.default_backend() == "tpu"
    records = 8                      # serve.run's execution batch
    workloads = {}
    for cfg, engine in serve.icu_engines().items():
        x, _ = icu.generate(cfg, records, seed=1)
        hlo = jax.jit(engine.model.forward).lower(
            engine.params, x).compile().as_text()
        kernel = "tpu_custom_call" in hlo
        _check(kernel == on_tpu,
               f"{cfg.name}: lstm_cell custom call in HLO: {kernel}, "
               f"expected {on_tpu}")
        logits, _ = engine.infer(jnp.asarray(x))
        want = reference_logits(engine.params, x, cfg)
        err = float(jnp.max(jnp.abs(logits - want)))
        _check(logits.shape == want.shape == (records, cfg.num_classes),
               f"{cfg.name}: logits shape {logits.shape}")
        _check(bool(jnp.all(jnp.isfinite(logits))),
               f"{cfg.name}: non-finite logits")
        _check(err <= LOGITS_ATOL,
               f"{cfg.name}: logits max |err| {err} > {LOGITS_ATOL}")
        workloads[cfg.name] = {"kernel_in_hlo": kernel,
                               "logits_max_abs_err": err,
                               "logits_max_abs": float(jnp.max(
                                   jnp.abs(want)))}
    return {"ours": ours, "lower_bound": lb,
            "baselines": {k: s.weighted_sum for k, s in results.items()
                          if k != OURS},
            "device_search": on_device, "workloads": workloads}


def _regime(movable: int, rows: int) -> str:
    """The batched search's regime for `movable` jobs per ward padded to
    `rows` rows (slots are bucketed as `tabu_search_batched` does)."""
    return scheduler_jax.kernel_regime(
        min(rows, scheduler._bucket16(movable)), rows)


def _claims_match(objs, assigns, specs, mpt, reserved) -> float:
    """Largest relative gap between the device search's own objective
    and `simulate` of the assignment it returned; raises past
    OBJECTIVE_RTOL."""
    worst = 0.0
    for b, jobs in enumerate(specs):
        plan = [MACHINES[int(m)] for m in assigns[b]]
        exact = simulate(jobs, plan, machines_per_tier=mpt,
                         reserved=reserved[b]).weighted_sum
        rel = abs(float(objs[b]) - exact) / max(abs(exact), 1.0)
        _check(rel <= OBJECTIVE_RTOL,
               f"ward {b}: device objective {float(objs[b])} vs "
               f"simulate {exact} (rel {rel})")
        worst = max(worst, rel)
    return worst


def _device_claims(plan, mpt, max_count: int) -> dict:
    """Replay the two device calls `scheduler.search_fleet` makes for
    `plan`: the independent plans, then its first sweep against the
    other wards' cloud jobs as interval reservations. The replayed
    independent plans must be serve's own, and each call's claimed
    objectives must match `simulate`."""
    specs = [[e.job for e in s.entries] for s in plan.naive_fleet.wards]
    pairs = [(mpt[CC], mpt[ES])] * len(specs)
    n = max(map(len, specs))
    rows = scheduler._bucket16(n)
    # reprolint: disable=R006
    objs, assigns = scheduler_jax.tabu_search_batched(
        specs, max_rounds=max_count, machines_per_tier=pairs, pad_to=rows)
    _check([[MACHINES[int(m)] for m in a] for a in assigns]
           == plan.naive_assignments,
           "device search did not reproduce the independent plans")
    naive_err = _claims_match(objs, assigns, specs, mpt,
                              [None] * len(specs))

    resvs = scheduler._fleet_reservations(specs, plan.naive_assignments,
                                          (CC,))
    raw = max(len(jobs) + sum(map(len, r.values()))
              for jobs, r in zip(specs, resvs))
    pad_to = -(-raw // 64) * 64          # search_fleet's pad_bucket
    # reprolint: disable=R006
    objs, assigns = scheduler_jax.tabu_search_batched(
        specs, [[MACHINES.index(t) for t in a]
                for a in plan.naive_assignments],
        max_rounds=2, machines_per_tier=pairs, reserved=resvs,
        pad_to=pad_to)
    sweep_err = _claims_match(objs, assigns, specs, mpt, resvs)
    return {"naive_rows": rows, "sweep_rows": pad_to,
            "regimes": [_regime(n, rows), _regime(n, pad_to)],
            "naive_max_rel_err": naive_err, "sweep_max_rel_err": sweep_err}


def _fleet_summary(plan) -> dict:
    naive = plan.naive_fleet.weighted_sum
    fleet = plan.fleet.weighted_sum
    _check(fleet <= naive, f"fleet-true {fleet} > naive {naive}")
    _check(plan.gap_closed > 0, f"gap closed {plan.gap_closed}")
    return {"wards": len(plan.assignments),
            "naive_claimed": plan.naive_reported,
            "naive_fleet_true": naive, "fleet_true": fleet,
            "contention_gap": plan.contention_gap,
            "gap_closed": plan.gap_closed, "sweeps": plan.sweeps}


def phase_fleet(wards: int = 32, patients: int = 100) -> dict:
    """Contention-aware fleet planning, twice.

    `serve.run_wards` plans serve's own patient fleet. The batched search
    then plans the contention fleet of BENCH_scheduler.json
    (`contention_interval`: `problems.metro_jobs`, 4 cloud and 2 edge
    machines), where independent plans really double-book the cloud and
    the sweeps run the kernel's pass regime. Each plan's device claims
    are checked against `simulate`."""
    _, seconds, plan = serve.run_wards(wards=wards, patients=patients,
                                       contention=True, verbose=False)
    mpt = {t: spec.machines for t, spec in paper_tiers().items()
           if not spec.private}
    served = {**_fleet_summary(plan), "plan_seconds": seconds,
              **_device_claims(plan, mpt, max_count=50)}

    instances = [metro_jobs(np.random.default_rng(5000 + i), n=patients)
                 for i in range(wards)]
    mpt = {CC: 4, ES: 2}
    plan = scheduler.search_fleet(instances, machines_per_tier=mpt,
                                  max_count=5, max_sweeps=4)
    contention = {**_fleet_summary(plan),
                  **_device_claims(plan, mpt, max_count=5)}
    _check(plan.contention_gap > 1,
           f"contention gap {plan.contention_gap}: no double-booking")
    _check(contention["regimes"] == ["round", "pass"],
           f"kernel regimes {contention['regimes']}")
    return {"serve": served, "contention": contention}


def phase_metro(wards=None, hours=None) -> dict:
    """serve.run_metro on `mass_casualty_crash` (its canonical wards and
    hours by default) with every replan on the device search, the
    sanitizer armed and each policy replayed twice."""
    with spans.recording() as rec:
        out = serve.run_metro(wards=wards, hours=hours,
                              scenario="mass_casualty_crash",
                              policies=("greedy", "tabu", "fleet"),
                              sanitize=True, check_determinism=True,
                              jax_threshold=0, verbose=False)
    device_searches = _device_searches(rec)
    _check(device_searches > 0, "no replan reached the device search")
    policies = {name: {"event_log_crc": f"{s['event_log_hash']:#010x}",
                       "completions": s["completions"],
                       "miss_rate": s["miss_rate"],
                       "critical_miss_rate": s["critical_miss_rate"]}
                for name, s in out.items()}
    return {"device_searches": device_searches, "policies": policies}


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"{dev.platform!r}; no phase was run", file=sys.stderr)
        return 1
    enable_compilation_cache()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    print(f"[bring-up] device {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}, jax {jax.__version__}")
    for name, phase in (("icu", phase_icu), ("fleet", phase_fleet),
                        ("metro", phase_metro)):
        n0, t0 = len(compiles), time.perf_counter()
        result = phase()
        wall = time.perf_counter() - t0
        print(f"[bring-up] phase {name}: wall {wall:.3f} s, "
              f"{len(compiles) - n0} compiles "
              f"({sum(compiles[n0:]):.3f} s), checks passed")
        print(f"[bring-up] phase {name} checks: "
              f"{json.dumps(result, sort_keys=True)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
