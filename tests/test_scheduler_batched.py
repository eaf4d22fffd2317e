"""Batched fleet-scale scheduling (DESIGN.md §8): padding/masking
semantics, batched-vs-per-instance parity, the search_batched dispatch,
the fleet-aware stochastic search, and the ValueError busy guards.

Instances are integer-valued (float32-exact), so "identical trajectories"
is testable as bit-identical objectives after exact re-simulation."""
import functools
import os
import sys

import numpy as np
import pytest

from prop import sweep
from repro.core import online, scheduler, scheduler_jax
from repro.core.problems import ward_batch
from repro.core.simulator import MACHINES, JobSpec, Reservation, simulate
from repro.core.tiers import CC, ED, ES


def _random_jobs(rng, n):
    return [JobSpec(name=f"J{i}", release=float(rng.integers(0, 30)),
                    weight=float(rng.integers(1, 4)),
                    proc={t: float(rng.integers(1, 30)) for t in MACHINES},
                    trans={CC: float(rng.integers(0, 60)),
                           ES: float(rng.integers(0, 15)), ED: 0.0})
            for i in range(n)]


def _random_fleet(rng):
    """(machines_per_tier pair, busy_until pair) with some machines deep
    busy and some idle."""
    mpt = (int(rng.integers(1, 4)), int(rng.integers(1, 4)))
    busy = tuple(
        [float(rng.choice([0.0, float(rng.integers(1, 40))]))
         for _ in range(int(rng.integers(0, m + 1)))]
        for m in mpt)
    return mpt, busy


def _exact(jobs, assign, mpt=(1, 1), busy=None, objective="weighted"):
    s = simulate(jobs, [MACHINES[int(i)] for i in assign],
                 machines_per_tier={CC: mpt[0], ES: mpt[1]},
                 busy_until=None if busy is None
                 else {CC: busy[0], ES: busy[1]})
    return {"weighted": s.weighted_sum, "unweighted": s.unweighted_sum,
            "last": s.last_end}[objective]


def _assert_batch_parity(batch, mpts, busys, objective="weighted"):
    """Batched search == per-instance (B = 1) runs, bit-identical after
    exact re-simulation, and reported values match the simulator."""
    vals, assigns = scheduler_jax.tabu_search_batched(
        batch, objective=objective, machines_per_tier=mpts,
        busy_until=busys)
    for jobs, mpt, busy, vb, ab in zip(batch, mpts, busys, vals, assigns):
        assert len(ab) == len(jobs)
        _, (a1,) = scheduler_jax.tabu_search_batched(
            [jobs], objective=objective, machines_per_tier=[mpt],
            busy_until=[busy])
        got = _exact(jobs, ab, mpt, busy, objective)
        solo = _exact(jobs, a1, mpt, busy, objective)
        assert got == solo, (got, solo)
        assert abs(vb - got) < 1e-3, (vb, got)


class TestBatchedParity:
    def test_mixed_sizes_fast(self):
        """Small fast-tier case: mixed ward sizes force phantom padding."""
        batch = [_random_jobs(np.random.default_rng(50 + i), n)
                 for i, n in enumerate((4, 11, 7))]
        B = len(batch)
        _assert_batch_parity(batch, [(1, 1)] * B, [None] * B)

    def test_fleet_and_busy_fast(self):
        """(2,3) fleet with occupied machines, single fast case."""
        batch = [_random_jobs(np.random.default_rng(60 + i), n)
                 for i, n in enumerate((6, 9))]
        mpts = [(2, 3), (1, 2)]
        busys = [([5.0, 17.0], [0.0, 3.0, 21.0]), (None)]
        _assert_batch_parity(batch, mpts, busys)

    # job counts drawn from a fixed grid so jit caches stay warm across
    # sweep cases (DESIGN.md §6)
    N_GRID = (4, 9, 14)

    @pytest.mark.slow
    @pytest.mark.parametrize("objective", ["weighted", "unweighted",
                                           "last"])
    def test_parity_sweep(self, objective):
        """Mixed-size batches, mixed fleets incl (2,3), nonzero
        busy_until — batched trajectories identical to solo runs."""
        def check(rng):
            B = int(rng.integers(2, 5))
            batch = [_random_jobs(rng, int(rng.choice(self.N_GRID)))
                     for _ in range(B)]
            fleets = [_random_fleet(rng) for _ in range(B)]
            if rng.integers(2):          # half the cases: uniform fleet
                fleets = [fleets[0]] * B
            _assert_batch_parity(batch, [f[0] for f in fleets],
                                 [f[1] for f in fleets], objective)
        sweep(check, n_cases=6, seed={"weighted": 0, "unweighted": 100,
                                      "last": 200}[objective])

    @pytest.mark.slow
    def test_parity_explicit_23_fleet_sweep(self):
        """The acceptance fleet: every ward on (2, 3) with busy machines."""
        def check(rng):
            B = int(rng.integers(2, 5))
            batch = [_random_jobs(rng, int(rng.choice(self.N_GRID)))
                     for _ in range(B)]
            busys = [([float(rng.integers(0, 25))],
                      [float(rng.integers(0, 25)),
                       float(rng.integers(0, 25))]) for _ in range(B)]
            _assert_batch_parity(batch, [(2, 3)] * B, busys)
        sweep(check, n_cases=5, seed=300)

    @pytest.mark.slow
    def test_ward_batch_generator_plans(self):
        """problems.ward_batch feeds search_batched end-to-end: every
        scenario yields valid exact schedules for mixed-size wards."""
        rng = np.random.default_rng(7)
        for scenario in ("poisson", "surge", "quiet"):
            batch = ward_batch(rng, 4, n_lo=4, n_hi=10, scenario=scenario)
            scheds = scheduler.search_batched(batch, max_count=5,
                                              min_batch=1)
            for jobs, s in zip(batch, scheds):
                assert len(s.entries) == len(jobs)
                ref = simulate(jobs, s.assignment())
                assert s.weighted_sum == ref.weighted_sum


class TestPhantomPadding:
    def test_phantoms_contribute_zero(self):
        """A ward padded next to a larger one returns exactly its solo
        objective — phantom jobs add 0 to every objective."""
        small = _random_jobs(np.random.default_rng(1), 4)
        big = _random_jobs(np.random.default_rng(2), 15)
        for objective in ("weighted", "unweighted", "last"):
            vals, assigns = scheduler_jax.tabu_search_batched(
                [small, big], objective=objective)
            (v_solo,), _ = scheduler_jax.tabu_search_batched(
                [small], objective=objective)
            assert vals[0] == v_solo
            assert len(assigns[0]) == 4

    def test_greedy_probe_matches_python_greedy(self):
        """max_rounds=0 returns the greedy initial — and the in-graph
        batched greedy is the same schedule as greedy_schedule."""
        def check(rng):
            jobs = _random_jobs(rng, int(rng.integers(2, 15)))
            mpt, busy = _random_fleet(rng)
            py = scheduler.greedy_schedule(
                jobs, machines_per_tier={CC: mpt[0], ES: mpt[1]},
                busy_until={CC: busy[0], ES: busy[1]})
            _, assigns = scheduler_jax.tabu_search_batched(
                [jobs], max_rounds=0, machines_per_tier=[mpt],
                busy_until=[busy])
            assert [MACHINES[int(i)] for i in assigns[0]] == py
        sweep(check, n_cases=10, seed=400)

    def test_empty_batch_and_empty_ward(self):
        vals, assigns = scheduler_jax.tabu_search_batched([])
        assert len(vals) == 0 and assigns == []
        vals, assigns = scheduler_jax.tabu_search_batched(
            [[], _random_jobs(np.random.default_rng(0), 5)])
        assert vals[0] == 0.0 and len(assigns[0]) == 0
        assert len(assigns[1]) == 5


def _packed_case(size, regime):
    """(batch, tabu_search_batched kwargs) for one ward alone ("B1") or a
    mixed-size three-ward batch ("B3") with reservations, frozen jobs and
    phantom machines, padded (pad_to) into the `regime` it names."""
    rng = np.random.default_rng(70)
    if size == "B1":
        batch = [_random_jobs(rng, 10)]
        kw = dict(machines_per_tier=(1, 1), busy_until=[([3.0], [])],
                  max_rounds=4)
    else:
        batch = [_random_jobs(rng, n) for n in (4, 11, 7)]
        resv = {CC: [Reservation(arrival=12.0, proc=9.0, release=2.0,
                                 weight=2.0)],
                ES: [Reservation(arrival=4.0, proc=6.0, release=4.0)]}
        kw = dict(initial=[[int(x) for x in rng.integers(0, 3, len(j))]
                           for j in batch],
                  machines_per_tier=[(1, 1), (2, 3), (1, 2)],
                  busy_until=[None, ([5.0, 17.0], [0.0, 3.0]), ([8.0], [])],
                  frozen=[None, [k % 3 == 0 for k in range(11)], None],
                  reserved=[None, None, resv])
    if regime == "pass":
        kw["pad_to"] = 48        # 16 movable slots in 48 rows
    return batch, kw


def _captured_search(monkeypatch, batch, kw):
    """Run tabu_search_batched, keeping the packed buffer, its layout and
    the static arguments the dispatch was handed."""
    seen = {}
    packed = scheduler_jax._tabu_run_packed

    def spy(buf, layout, objective, **static):
        seen.update(buf=buf.copy(), layout=layout, objective=objective,
                    **static)
        return packed(buf, layout, objective, **static)
    monkeypatch.setattr(scheduler_jax, "_tabu_run_packed", spy)
    return scheduler_jax.tabu_search_batched(batch, **kw), seen


@functools.cache
def _unpacked_kernel():
    """The search's traced body jitted alone, on the eleven arguments
    that `_tabu_run_packed` unpacks from its buffer."""
    import jax
    return jax.jit(scheduler_jax._tabu_body,
                   static_argnames=("objective", "greedy_init", "mode"))


def _host_inputs(seen):
    """`_tabu_body`'s eleven host arguments read off the buffer:
    float32 and int32 fields as they are, bools from 0/1."""
    views = scheduler_jax._field_views(seen["buf"], seen["layout"])
    kinds = dict(scheduler_jax._PACKED_FIELDS)
    out = {name: v != 0 if kinds[name] == "b" else v
           for name, v in views.items()}
    out["max_rounds"] = np.int32(out["max_rounds"])
    return out


@pytest.mark.parametrize("size", ["B1", "B3"])
@pytest.mark.parametrize("regime", ["round", "pass"])
class TestPackedBoundary:
    """The search crosses to the device as one int32 buffer and back as
    one array (DESIGN.md §8 "The packed boundary")."""

    def test_fields_round_trip_bit_for_bit(self, monkeypatch, size,
                                           regime):
        import jax
        batch, kw = _packed_case(size, regime)
        _, seen = _captured_search(monkeypatch, batch, kw)
        assert seen["mode"] == regime
        host = _host_inputs(seen)
        got = jax.jit(scheduler_jax._unpack, static_argnums=1)(
            seen["buf"], seen["layout"])
        kinds = dict(scheduler_jax._PACKED_FIELDS)
        assert len(got) == len(kinds) == 11
        for (name, kind), g in zip(scheduler_jax._PACKED_FIELDS, got):
            g, want = np.asarray(g), host[name]
            assert g.shape == want.shape, name
            assert g.dtype == {"f": np.float32, "b": np.bool_,
                               "i": np.int32}[kind], name
            if kind == "f":                     # bits, +inf and -0.0 too
                assert np.array_equal(g.view(np.int32),
                                      want.view(np.int32)), name
            else:
                assert np.array_equal(g, want), name
        # what the fields hold: the jobs, the movable slots, the rounds
        # and +inf-busy phantom machines
        views = scheduler_jax._field_views(seen["buf"], seen["layout"])
        assert set(np.unique(views["movable"])) <= {0, 1}
        assert set(np.unique(views["mov_ok"])) <= {0, 1}
        for b, jobs in enumerate(batch):
            assert host["rel"][b, :len(jobs)].tolist() == \
                [j.release for j in jobs]
            assert host["mov_ok"][b].sum() == host["movable"][b].sum()
        assert host["max_rounds"] == kw.get("max_rounds", 50)
        mpts = kw["machines_per_tier"]
        mpts = [mpts] * len(batch) if len(mpts) == 2 and \
            isinstance(mpts[0], int) else mpts
        for b, (mc, me) in enumerate(mpts):
            assert np.isposinf(host["busy_c"][b, mc:]).all()
            assert np.isposinf(host["busy_e"][b, me:]).all()
        if size == "B3":
            assert np.isposinf(host["busy_c"]).any()
            assert not host["movable"][1, 0] and host["movable"][1, 1]

    def test_search_matches_the_unpacked_kernel(self, monkeypatch, size,
                                                regime):
        batch, kw = _packed_case(size, regime)
        (vals, assigns), seen = _captured_search(monkeypatch, batch, kw)
        host = _host_inputs(seen)
        assign, totals, _, _ = _unpacked_kernel()(
            *(host[name] for name, _ in scheduler_jax._PACKED_FIELDS),
            seen["objective"], greedy_init=seen["greedy_init"],
            mode=seen["mode"])
        assert np.array_equal(vals, np.asarray(totals, np.float64))
        assert vals.dtype == np.float64
        for b, jobs in enumerate(batch):
            assert assigns[b].dtype == np.int32
            assert np.array_equal(assigns[b],
                                  np.asarray(assign)[b, :len(jobs)])


def _width1_reference(assign0, mov_idx, mov_ok, tc, dev, oi, max_rounds):
    """The plain reference of the `pass` regime: the accept-as-you-go
    walk of S width-1 slot evaluations per pass, as the kernel ran it
    before the first-improvement replay. Returns (assign, totals,
    rounds)."""
    import jax
    import jax.numpy as jnp
    B = assign0.shape[0]
    S = mov_idx.shape[1]
    binds = jnp.arange(B)

    def cond(state):
        _, _, rnd, active = state
        return jnp.any(active) & (rnd < max_rounds)

    def body(state):
        assign, _, rnd, active = state

        def slot(carry, s):
            assign, total, changed = carry
            k = jnp.take(mov_idx, s, axis=1)
            ok = jnp.take(mov_ok, s, axis=1) & active
            tot, vals = scheduler_jax._round_batched(
                assign, k[:, None], ok[:, None], tc, dev, oi)
            flat = vals[:, 0, :]
            m1 = jnp.argmin(flat, axis=1)
            v1 = jnp.take_along_axis(flat, m1[:, None], axis=1)[:, 0]
            improved = v1 < tot
            assign = assign.at[binds, k].set(
                jnp.where(improved, m1.astype(assign.dtype),
                          assign[binds, k]))
            total = jnp.where(improved, v1, tot)
            return (assign, total, changed | improved), None

        (assign, total, changed), _ = jax.lax.scan(
            slot, (assign, jnp.full((B,), jnp.inf), jnp.zeros((B,), bool)),
            jnp.arange(S))
        return assign, total, rnd + 1, changed

    state = (assign0, jnp.full((B,), jnp.inf), jnp.int32(0),
             jnp.ones((B,), bool))
    assign, totals, rounds, _ = jax.lax.while_loop(cond, body, state)
    totals = jax.lax.cond(
        rounds == 0,
        lambda args: scheduler_jax._round_batched(
            args[0], mov_idx, mov_ok, tc, dev, oi)[0],
        lambda args: args[1], (assign, totals))
    return assign, totals, rounds


def _pass_runs(host, objective):
    """-> {name: (assign, totals, rounds)} of the packed inputs `host`
    under the reference walk, the replay at any B (`_pass_replay` through
    `_run_passes`) and the kernel as dispatched."""
    import jax
    oi = scheduler_jax._OBJ_IDX[objective]
    args = [host[name] for name, _ in scheduler_jax._PACKED_FIELDS]

    @jax.jit
    def both(assign0, rel, w, proc, trans, movable, mov_idx, mov_ok,
             max_rounds, busy_c, busy_e):
        tc, dev = scheduler_jax._kernel_consts(rel, w, proc, trans,
                                               busy_c, busy_e)
        ref = _width1_reference(assign0, mov_idx, mov_ok, tc, dev, oi,
                                max_rounds)
        rep = scheduler_jax._run_passes(assign0, mov_idx, mov_ok, tc, dev,
                                        oi, max_rounds,
                                        scheduler_jax._pass_replay)
        return ref, rep[:3], rep[3]

    ref, rep, evals = both(*args)
    kernel = _unpacked_kernel()(*args, objective, mode="pass")
    out = {"reference": ref, "replay": rep, "kernel": kernel[:3]}
    return {k: tuple(np.asarray(x) for x in v) for k, v in out.items()}, \
        int(evals)


def _metro_ward(rng, n_jobs, objective, fleet, n_resv=27, converged=False,
                last_bad=False):
    """One ward in the metro replan's B = 1 shape: `n_jobs` movable jobs
    from a random initial and `n_resv` reservation rows of other wards'
    cloud work (a few on the edge), padded to 48 rows and 16 slots.
    `last_bad` starts the last movable job on a device it is far too slow
    on, so its slot accepts; `converged` starts from the ward's own
    searched plan, so its first pass accepts nothing."""
    jobs = _random_jobs(rng, n_jobs)
    if last_bad:
        j = jobs[-1]
        jobs[-1] = JobSpec(name=j.name, release=j.release, weight=j.weight,
                           proc={**j.proc, ED: 500.0}, trans=j.trans)
    resv = {CC: [Reservation(arrival=float(rng.integers(0, 60)),
                             proc=float(rng.integers(1, 20)),
                             release=float(rng.integers(0, 30)),
                             weight=float(rng.integers(1, 3)))
                 for _ in range(n_resv - n_resv // 9)],
            ES: [Reservation(arrival=float(rng.integers(0, 40)),
                             proc=float(rng.integers(1, 10)),
                             release=float(rng.integers(0, 30)))
                 for _ in range(n_resv // 9)]}
    initial = [int(x) for x in rng.integers(0, 3, n_jobs)]
    if last_bad:
        initial[-1] = 2
    busy = ([float(rng.integers(0, 20))], [])
    if converged:
        _, (plan,) = scheduler_jax.tabu_search_batched(
            [jobs], [initial], objective=objective,
            machines_per_tier=[fleet], busy_until=[busy], reserved=[resv])
        initial = [int(x) for x in plan]
    return jobs, initial, resv, busy


# (objective, fleet, movable jobs per ward, max_rounds, ward options)
_PASS_CASES = {
    "weighted-1x1": ("weighted", (1, 1), (6,), 50, [{}]),
    "unweighted-15x1": ("unweighted", (15, 1), (8,), 50, [{}]),
    "last-2x3": ("last", (2, 3), (5,), 50, [{}]),
    "weighted-15x1": ("weighted", (15, 1), (10,), 50, [{}]),
    "unweighted-2x3": ("unweighted", (2, 3), (3,), 50, [{}]),
    "last-15x1": ("last", (15, 1), (7,), 50, [{}]),
    "cap-hit": ("weighted", (15, 1), (10,), 1, [{}]),
    "last-real-slot-accepts": ("weighted", (15, 1), (4,), 50,
                               [{"last_bad": True}]),
    "slot-S-1-accepts": ("unweighted", (2, 3), (16,), 50,
                         [{"last_bad": True}]),
    "B3-one-ward-inactive": ("weighted", (15, 1), (9, 4, 6), 50,
                             [{}, {"converged": True}, {"last_bad": True}]),
    "B3-last-2x3": ("last", (2, 3), (2, 10, 5), 50, [{}, {}, {}]),
}


@pytest.mark.parametrize("case", list(_PASS_CASES))
def test_first_improvement_pass_equals_the_width1_walk(monkeypatch, case):
    """The `pass` regime's replay (DESIGN.md §12) gives the reference
    walk's assignments, objective bits and pass count on every ward."""
    objective, fleet, sizes, max_rounds, opts = _PASS_CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    wards = [_metro_ward(rng, n, objective, fleet, **o)
             for n, o in zip(sizes, opts)]
    batch = [w[0] for w in wards]
    kw = dict(initial=[w[1] for w in wards], reserved=[w[2] for w in wards],
              busy_until=[w[3] for w in wards],
              machines_per_tier=[fleet] * len(wards),
              max_rounds=max_rounds, objective=objective, pad_to=48)
    _, seen = _captured_search(monkeypatch, batch, kw)
    assert seen["mode"] == "pass" and seen["layout"][1:3] == (48, 16)
    runs, evals = _pass_runs(_host_inputs(seen), objective)
    ref_assign, ref_totals, ref_rounds = runs["reference"]
    for name in ("replay", "kernel"):
        assign, totals, rounds = runs[name]
        assert np.array_equal(assign, ref_assign), name
        assert np.array_equal(totals.view(np.int32),
                              ref_totals.view(np.int32)), name
        assert rounds == ref_rounds, name
    assign0 = _host_inputs(seen)["assign0"]
    moved = (ref_assign != assign0).any(axis=1)
    assert moved.any()                       # the walk had work to do
    assert evals <= ref_rounds * 16
    if case == "cap-hit":
        assert ref_rounds == 1
        uncapped = scheduler_jax.tabu_search_batched(
            batch, **dict(kw, max_rounds=50))[1][0]
        assert not np.array_equal(uncapped, ref_assign[0, :len(batch[0])])
    if case == "B3-one-ward-inactive":
        assert not moved[1]
    if "accepts" in case:
        last = len(batch[0]) - 1
        assert ref_assign[0, last] != 2 and assign0[0, last] == 2


def test_a_batch_equals_each_ward_searched_alone():
    """B > 1 takes the width-1 walk and B = 1 the replay: every ward of a
    batch comes out bit for bit as its own B = 1 search."""
    rng = np.random.default_rng(17)
    for objective, fleet in (("weighted", (15, 1)), ("last", (2, 3))):
        wards = [_metro_ward(rng, n, objective, fleet, **o) for n, o in
                 zip((9, 4, 6), ({}, {"converged": True}, {}))]
        vals, assigns = scheduler_jax.tabu_search_batched(
            [w[0] for w in wards], [w[1] for w in wards],
            objective=objective, machines_per_tier=fleet,
            busy_until=[w[3] for w in wards],
            reserved=[w[2] for w in wards], pad_to=48)
        for (jobs, initial, resv, busy), v, a in zip(wards, vals, assigns):
            v1, a1 = scheduler_jax.tabu_search_batched(
                [jobs], [initial], objective=objective,
                machines_per_tier=fleet, busy_until=[busy],
                reserved=[resv], pad_to=48)
            assert np.array_equal(a, a1[0])
            assert np.float32(v).view(np.int32) == \
                np.float32(v1[0]).view(np.int32)


def _fetched(monkeypatch, armed):
    """The attrs set on `scheduler.fetch` by one two-job search padded
    into the `pass` regime: job A starts on a device it is far too slow
    on and moves to the edge at slot 0; job B already sits on the edge,
    and the cloud's transfer is too long for either. One move, two
    passes (the second accepts nothing). Unarmed, the attrs the program
    sets on the shared no-op span."""
    from repro.utils import spans
    jobs = [JobSpec(name=n, release=0.0, weight=1.0,
                    proc={CC: 1.0, ES: 2.0, ED: 900.0},
                    trans={CC: 500.0, ES: 1.0, ED: 0.0}) for n in "AB"]
    if not armed:
        seen = [{}]
        monkeypatch.setattr(spans._Off, "set",
                            lambda self, **attrs: seen[0].update(attrs))
        _, assigns = scheduler_jax.tabu_search_batched(
            [jobs], [[2, 1]], pad_to=48)
        return assigns[0].tolist(), seen
    with spans.recording() as rec:
        _, assigns = scheduler_jax.tabu_search_batched(
            [jobs], [[2, 1]], pad_to=48)
    return assigns[0].tolist(), [s.attrs for s in rec.spans
                                 if s.name == "scheduler.fetch"]


def test_pass_evals_counts_moves_plus_passes(monkeypatch):
    assign, (fetch,) = _fetched(monkeypatch, True)
    assert assign == [1, 1]                  # the one move: A to the edge
    assert fetch["pass_evals"] == 1 + 2 and fetch["d2h_arrays"] == 1


@pytest.mark.parametrize("why", ["unarmed", "round"])
def test_pass_evals_only_on_armed_pass_searches(monkeypatch, why):
    if why == "unarmed":
        assign, (fetch,) = _fetched(monkeypatch, False)
    else:                                    # 16 rows: the round regime
        from repro.utils import spans
        jobs = _random_jobs(np.random.default_rng(3), 6)
        with spans.recording() as rec:
            scheduler_jax.tabu_search_batched([jobs])
        (fetch,) = [s.attrs for s in rec.spans
                    if s.name == "scheduler.fetch"]
        (dispatch,) = [s.attrs for s in rec.spans
                       if s.name == "scheduler.dispatch"]
        assert dispatch["regime"] == "round"
    assert "pass_evals" not in fetch


class TestSearchBatchedDispatch:
    def test_batched_path_returns_exact_schedules(self):
        problems = [_random_jobs(np.random.default_rng(10 + i), n)
                    for i, n in enumerate((8, 13, 5, 10))]
        mpt = {CC: 2, ES: 1}
        scheds = scheduler.search_batched(problems, max_count=5,
                                          machines_per_tier=mpt,
                                          min_batch=1)
        for jobs, s in zip(problems, scheds):
            ref = simulate(jobs, s.assignment(), machines_per_tier=mpt)
            assert s.weighted_sum == ref.weighted_sum
            for t in MACHINES:
                assert s.weighted_sum <= scheduler.all_on_tier(
                    jobs, t, machines_per_tier=mpt).weighted_sum + 1e-6

    def test_sequential_fallback_below_min_batch(self):
        problems = [_random_jobs(np.random.default_rng(20 + i), 7)
                    for i in range(2)]
        a = scheduler.search_batched(problems, min_batch=10)
        b = [scheduler.search(p) for p in problems]
        for s1, s2 in zip(a, b):
            assert s1.weighted_sum == s2.weighted_sum

    def test_per_ward_fleets_and_busy(self):
        problems = [_random_jobs(np.random.default_rng(30 + i), 9)
                    for i in range(4)]
        mpts = [{CC: 1, ES: 1}, {CC: 2, ES: 3}, {CC: 1, ES: 2},
                {CC: 3, ES: 1}]
        busys = [None, {CC: [4.0], ES: [2.0, 9.0]}, None, {CC: [7.0]}]
        scheds = scheduler.search_batched(problems, max_count=5,
                                          machines_per_tier=mpts,
                                          busy_until=busys, min_batch=1)
        for jobs, m, b, s in zip(problems, mpts, busys, scheds):
            ref = simulate(jobs, s.assignment(), machines_per_tier=m,
                           busy_until=b)
            assert s.weighted_sum == ref.weighted_sum

    def test_competitive_ratio_batch_matches_solo(self):
        instances = [_random_jobs(np.random.default_rng(40 + i), 8)
                     for i in range(3)]
        ratios = online.competitive_ratio_batch(
            instances, replans=("greedy", "tabu"), min_batch=99)
        for replan in ("greedy", "tabu"):
            solo = [online.competitive_ratio(jobs, replan=replan)
                    for jobs in instances]
            assert np.allclose(ratios[replan], solo)


class TestStochasticFleet:
    def test_stochastic_search_scores_the_real_fleet(self):
        """The seed bug: candidates were scored on an idle (1,1) fleet.
        The claimed objective must now match the exact simulator under
        the deployed fleet and occupancy."""
        jobs = _random_jobs(np.random.default_rng(5), 12)
        mpt = (2, 3)
        busy = ([6.0, 14.0], [3.0])
        import jax
        initial = np.asarray(
            [MACHINES.index(t) for t in scheduler.greedy_schedule(
                jobs, machines_per_tier={CC: mpt[0], ES: mpt[1]},
                busy_until={CC: busy[0], ES: busy[1]})], np.int32)
        v, a = scheduler_jax.stochastic_search(
            jobs, jax.random.PRNGKey(0), initial, iters=30,
            machines_per_tier=mpt, busy_until=busy)
        exact = simulate(jobs, [MACHINES[int(i)] for i in a],
                         machines_per_tier={CC: mpt[0], ES: mpt[1]},
                         busy_until={CC: busy[0], ES: busy[1]})
        assert abs(v - exact.weighted_sum) < 1e-2


class TestBusyGuardsRaise:
    """The overfull-busy guards are ValueError, not assert — they must
    survive ``python -O`` (DESIGN.md §7)."""

    def test_normalize_busy_overfull(self):
        with pytest.raises(ValueError):
            scheduler_jax._normalize_busy(([1.0, 2.0], ()), (1, 1))

    def test_busy_vectors_overfull(self):
        jobs = _random_jobs(np.random.default_rng(0), 2)
        commits = [online._Commit(jobs[0], CC, 0.0, 0.0, 50.0),
                   online._Commit(jobs[1], CC, 0.0, 0.0, 60.0)]
        with pytest.raises(ValueError):
            online._busy_vectors(commits, [], now=10.0,
                                 machines_per_tier={CC: 1, ES: 1})

    def test_mpt_length_mismatch(self):
        batch = [_random_jobs(np.random.default_rng(0), 3)] * 3
        with pytest.raises(ValueError):
            scheduler_jax.tabu_search_batched(
                batch, machines_per_tier=[(1, 1), (2, 2)])


class TestRegressionGate:
    """benchmarks/check_regression.py compare() logic (no bench run)."""

    def _reports(self):
        base = {
            "head_to_head": [
                {"n": 100, "methods": {
                    "incremental": {"seconds": 0.01,
                                    "speedup_vs_reference": 30.0},
                    "jax": {"seconds": 0.005,
                            "speedup_vs_reference": 60.0}}},
            ],
            "batched": {"speedup_batched_vs_sequential": 5.0,
                        "wards_per_s_batched": 600.0,
                        "parity_mismatches": 0},
        }
        import copy
        return base, copy.deepcopy(base)

    def _compare(self):
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir, "benchmarks"))
        try:
            from check_regression import compare
        finally:
            sys.path.pop(0)
        return compare

    def test_identical_reports_pass(self):
        compare = self._compare()
        committed, fresh = self._reports()
        assert compare(committed, fresh) == []

    def test_within_tolerance_passes(self):
        compare = self._compare()
        committed, fresh = self._reports()
        fresh["batched"]["speedup_batched_vs_sequential"] = 4.0  # -20%
        assert compare(committed, fresh, tolerance=0.30) == []

    def test_regression_fails(self):
        compare = self._compare()
        committed, fresh = self._reports()
        fresh["batched"]["wards_per_s_batched"] = 300.0          # -50%
        fresh["head_to_head"][0]["methods"]["jax"]["seconds"] = 0.02
        problems = compare(committed, fresh, tolerance=0.30)
        assert any("wards_per_s" in p for p in problems)
        assert any("jax_vs_incremental" in p for p in problems)

    def test_parity_mismatch_fails(self):
        compare = self._compare()
        committed, fresh = self._reports()
        fresh["batched"]["parity_mismatches"] = 2
        assert any("parity" in p for p in compare(committed, fresh))
