"""JAX-vectorised schedule evaluation and search (beyond-paper).

The paper's heuristic evaluates one candidate schedule at a time in Python.
For fleet-scale serving (thousands of jobs, many candidate assignments) we
evaluate assignment *batches* on-device. Two observations make the C1-C5
semantics fast to vectorise (DESIGN.md §3.2):

  * each shared tier's FIFO order key (arrival, release, index) depends
    only on the JOB SET, never on the candidate assignment — so the sort
    happens once per instance, not once per candidate;
  * the single-server FIFO recurrence e_j = max(arr_j, e_{j-1}) + p_j is
    an associative scan: with P_j = cumsum(p) in queue order,
    e_j = cummax_k<=j(arr_k - P_{k-1}) + P_j — evaluated with two
    parallel prefix ops, no sequential lax.scan. Non-members are masked
    transparent (p=0, arr=-inf). Multi-server tiers fall back to a
    free-slot lax.scan identical to the Python simulator's heap.

Used for:
  * exact small-n optimum: enumerate all 3^n assignments in one vmap;
  * `tabu_search_jax`: the fully jitted Algorithm-2 neighbourhood search —
    every lax.while_loop round scores the whole n x 3 single-move
    neighbourhood by DELTA EVALUATION (each candidate re-scores only its
    two affected tiers; one scan per shared tier yields all n toggled
    stats — DESIGN.md §3.2), so there are NO host<->device round trips
    until the search terminates;
  * `tabu_search_batched`: B independent ward instances searched in ONE
    device call — variable sizes padded with transparent phantom jobs,
    mixed fleets padded with +inf-busy phantom machines, per-instance
    convergence flags (DESIGN.md §8);
  * random-restart stochastic local search (kept for comparison; it syncs
    to NumPy every iteration);
  * jittable evaluation inside the serving engine's control loop.

Machine encoding: 0 = cloud, 1 = edge, 2 = device (private). Shared tiers
may have several identical machines (`machines_per_tier`, static): jobs
are dispatched FIFO to the earliest-free machine, exactly matching the
Python simulator's free-time heap. Queue order ties break by
(arrival, release, job index), again matching `simulate`.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.simulator import JobSpec, Reservation
from repro.core.tiers import CC, ED, ES
from repro.utils import spans

N_MACHINES = 3


def _specs_to_np(jobs: Sequence[JobSpec]):
    """Host-side (numpy) spec arrays — no device transfers (batch padding
    assembles B instances without B round trips), one pass over the jobs."""
    flat = np.asarray(
        [(j.release, j.weight, j.proc[CC], j.proc[ES], j.proc[ED],
          j.trans[CC], j.trans[ES], j.trans.get(ED, 0.0)) for j in jobs],
        np.float32).reshape(-1, 8)
    return flat[:, 0], flat[:, 1], flat[:, 2:5], flat[:, 5:8]


def specs_to_arrays(jobs: Sequence[JobSpec]):
    """-> release (n,), weight (n,), proc (n,3), trans (n,3)."""
    return tuple(jnp.asarray(x) for x in _specs_to_np(jobs))


def _tier_setup(rel, proc, trans, m: int):
    """Assignment-independent per-tier constants: the FIFO queue order
    (arrival, release, index — lexsort majors on its last key, stability
    gives the index tiebreak) and arrival/processing times in that order."""
    arr = rel + trans[:, m]
    order = jnp.lexsort((rel, arr))
    return order, arr[order], proc[:, m][order]


def _shared_ends_single(mask_s, arr_s, p_s, free0):
    """Completion times on a 1-machine tier, in queue order, via parallel
    prefix ops (no sequential scan): e = max(cummax(arr - P_prev), free0)
    + P. ``free0`` is the machine's initial free time (busy_until folded
    into the prefix as the virtual element before the first job)."""
    p_eff = jnp.where(mask_s, p_s, 0.0)
    csum = jnp.cumsum(p_eff)
    q = jnp.where(mask_s, arr_s, -jnp.inf) - (csum - p_eff)
    e = jnp.maximum(jax.lax.cummax(q), free0) + csum
    return jnp.where(mask_s, e, 0.0)


def _shared_ends_multi(mask_s, arr_s, p_s, busy):
    """Multi-machine tier: FIFO dispatch to the earliest-free machine (the
    vectorised analogue of the simulator's free-time heap). ``busy`` is the
    (cnt,) vector of initial machine free times (zeros when idle)."""

    def step(free, x):
        valid, arr, p = x
        slot = jnp.argmin(free)
        start = jnp.maximum(arr, free[slot])
        e = start + p
        return (jnp.where(valid, free.at[slot].set(e), free),
                jnp.where(valid, e, 0.0))

    _, ends = jax.lax.scan(step, busy.astype(arr_s.dtype),
                           (mask_s, arr_s, p_s))
    return ends


def _normalize_busy(busy_until, machines_per_tier: Tuple[int, int]):
    """-> ((m_cloud,), (m_edge,)) float32 arrays of initial machine free
    times, sorted, zero-padded to the machine count. Accepts None or a
    (cloud_times, edge_times) pair with <= machine entries per tier.

    Raises ValueError (not assert — guards must survive ``python -O``) when
    a caller lists more occupied machines than the tier has servers."""
    busy_until = busy_until or ((), ())
    out = []
    for vals, m in zip(busy_until, machines_per_tier):
        v = sorted(float(x) for x in np.asarray(vals).reshape(-1))
        if len(v) > m:
            raise ValueError(f"busy_until lists {len(v)} occupied machines "
                             f"for a {m}-machine tier")
        out.append(np.asarray([0.0] * (m - len(v)) + v, np.float32))
    return tuple(out)


def _make_eval(rel, w, proc, trans, machines_per_tier: Tuple[int, int],
               busy_until=None):
    """-> eval_one(a) computing {weighted, unweighted, last} for one
    assignment vector; the per-tier sorts are hoisted out so they run once
    per instance, not per candidate. busy_until: optional (cloud, edge)
    initial machine free-time arrays (see _normalize_busy)."""
    setups = [_tier_setup(rel, proc, trans, m) for m in (0, 1)]
    dev_end = rel + trans[:, 2] + proc[:, 2]
    if busy_until is None:
        busy_until = tuple(jnp.zeros((m,), jnp.float32)
                           for m in machines_per_tier)

    def eval_one(a):
        end = jnp.where(a == 2, dev_end, 0.0)       # private device tier
        for m, (order, arr_s, p_s), cnt, busy in zip(
                (0, 1), setups, machines_per_tier, busy_until):
            mask_s = (a == m)[order]
            if cnt == 1:
                e_s = _shared_ends_single(mask_s, arr_s, p_s, busy[0])
            else:
                e_s = _shared_ends_multi(mask_s, arr_s, p_s, busy)
            end = end.at[order].add(e_s)
        resp = end - rel
        return {"weighted": jnp.sum(w * resp),
                "unweighted": jnp.sum(resp),
                "last": jnp.max(end)}

    return eval_one


@functools.partial(jax.jit, static_argnames=("machines_per_tier",))
def _evaluate_assignments_jit(assign, rel, w, proc, trans, busy_until,
                              machines_per_tier: Tuple[int, int]):
    return jax.vmap(_make_eval(rel, w, proc, trans, machines_per_tier,
                               busy_until))(assign)


def evaluate_assignments(assign, rel, w, proc, trans,
                         machines_per_tier: Tuple[int, int] = (1, 1),
                         busy_until=None):
    """assign: (A, n) int32 in {0, 1, 2}. Returns dict of (A,) metrics.

    machines_per_tier: static (cloud, edge) shared-machine counts — the
    vectorised analogue of `simulate(..., machines_per_tier=...)`.
    busy_until: optional (cloud_times, edge_times) initial machine free
    times (the analogue of `simulate(..., busy_until=...)`); traced, so
    replans with changing availability reuse the same compiled kernel.
    """
    busy = _normalize_busy(busy_until, machines_per_tier)
    return _evaluate_assignments_jit(assign, rel, w, proc, trans, busy,
                                     machines_per_tier)


def exact_optimum_jax(jobs: Sequence[JobSpec], objective: str = "weighted",
                      batch: int = 65536,
                      machines_per_tier: Tuple[int, int] = (1, 1),
                      busy_until=None):
    """Enumerate all 3^n assignments on-device. Practical to n ~ 14."""
    n = len(jobs)
    rel, w, proc, trans = specs_to_arrays(jobs)
    total = N_MACHINES ** n
    powers = N_MACHINES ** np.arange(n)
    best_v, best_a = np.inf, None
    for lo in range(0, total, batch):
        codes = np.arange(lo, min(lo + batch, total))
        assign = jnp.asarray((codes[:, None] // powers[None]) % N_MACHINES,
                             jnp.int32)
        m = evaluate_assignments(assign, rel, w, proc, trans,
                                 machines_per_tier=machines_per_tier,
                                 busy_until=busy_until)
        vals = np.asarray(m[objective])
        i = int(np.argmin(vals))
        if vals[i] < best_v:
            best_v, best_a = float(vals[i]), np.asarray(assign[i])
    return best_v, best_a


# ------------------------------------- delta-evaluated jitted tabu search
#
# DESIGN.md §3.2/§8: a single-move candidate perturbs only its source and
# destination tiers, so a tabu round never re-evaluates whole assignments.
# Per round, each shared tier computes the incumbent stat plus all n
# "toggle job k's membership" stats in ONE scan over the tier's (hoisted)
# queue order — O(n^2) flops, O(n) memory, no (3n, n) candidate
# materialisation and no per-candidate cumsum/cummax. Candidate (k, m) is
# then scored from per-tier scalars: the toggled source stat, the toggled
# destination stat, and the incumbent's untouched third-tier stat.

_OBJ_IDX = {"weighted": 0, "unweighted": 1, "last": 2}


def _tier_rounds(mask_T, arr_T, p_T, w_T, rel_T, busy_T, ps, oi: int):
    """Incumbent + movable-position toggled stats of BOTH shared tiers of
    every instance in one scan.

    Inputs are stacked per-tier queue-order constants, shape (B, 2, n)
    (and (B, 2, m) machine free times — mixed fleets pad the smaller tier
    with +inf phantom machines, which FIFO dispatch never selects).
    ``ps`` (B, 2, S) lists the queue POSITIONS of each instance's movable
    jobs (DESIGN.md §12): toggled stats are only ever consumed for moves
    of movable jobs, so the carry tracks S toggle columns instead of n —
    a mostly-frozen ward (reservations, fleet background) costs
    O(movable) per round instead of O(n). Column s of the carry tracks
    the queue with the job at queue position ps[..., s] toggled (member
    removed / non-member inserted). Columns walk the queue once, so the
    whole B-instance 2-tier S-toggle neighbourhood costs one length-n
    scan whose per-step op count is independent of B and tier count (op
    dispatch, not flops, bounds CPU throughput — the batch rides along
    inside each op).

    All-single-server fleets (m == 1, the static shape of busy_T) carry
    the running cummax of q = arr − P_prev (the §3.2 prefix recurrence);
    multi-machine fleets carry per-row free-slot vectors (the vectorised
    free-time heap, start = max(arrival, earliest free) exactly as
    `simulate`). Returns ((B, 2) incumbent stats, (B, 2, S) toggled
    stats aligned with ps). Per toggle column the arithmetic is
    elementwise-identical to the old all-positions carry, so restricting
    to movable columns is a pure column gather — bit-identical values."""
    B, _, n = mask_T.shape
    m = busy_T.shape[2]
    S = ps.shape[2]

    def lead(x):                                # (B, 2, n) -> (n, B, 2)
        return jnp.moveaxis(x, 2, 0)

    def gat(x):                                 # (B, 2, n) -> (B, 2, S)
        return jnp.take_along_axis(x, ps, axis=2)

    if m == 1:
        p_eff = jnp.where(mask_T, p_T, 0.0)
        csum = jnp.cumsum(p_eff, axis=2)
        q = jnp.where(mask_T, arr_T, -jnp.inf) - (csum - p_eff)
        free0 = busy_T[:, :, :1]                # finite on 1-machine tiers
        delta = jnp.where(mask_T, -p_T, p_T)    # toggle's suffix p shift
        q_self = jnp.where(mask_T, -jnp.inf, arr_T - (csum - p_eff))
        cm = jax.lax.cummax(q, axis=2)          # M_j, the §3.2 prefix max
        e_inc = jnp.maximum(cm, free0) + csum   # incumbent completions
        # A toggle at position s leaves the queue prefix untouched and
        # shifts the suffix cumsum by delta_s, so with
        # K_s = max(M_{s-1}, q'_s, f0) and G_s = K_s + delta_s the
        # toggled completion of j > s is
        #   e'_j = max(K_s, R_{s+1,j} - delta_s) + C_j + delta_s
        #        = max(G_s, R_{s+1,j}) + C_j
        # (R = range max of q). Everything but the 2D range max reduces
        # to O(n) prefix/suffix sums of incumbent quantities.
        cm_prev = jnp.concatenate(
            [jnp.full((B, 2, 1), -jnp.inf), cm[:, :, :-1]], axis=2)
        K = jnp.maximum(jnp.maximum(cm_prev, q_self), free0)
        G = K + delta

        if oi != 2:
            wm = jnp.where(mask_T, w_T if oi == 0 else 1.0, 0.0)
            contrib = wm * (e_inc - rel_T)
            stat = jnp.sum(contrib, axis=2)
            cpre = jnp.cumsum(contrib, axis=2)
            pre = cpre - contrib                       # sum over j < s
            lin = wm * (csum - rel_T)
            clin = jnp.cumsum(lin, axis=2)
            suf_lin = clin[:, :, -1:] - clin           # sum over j > s
            wpre = jnp.cumsum(wm, axis=2)              # sum over j <= s
            own = jnp.where(
                mask_T, 0.0,
                (w_T if oi == 0 else 1.0) * (G + csum - rel_T))
            # T_s = sum_{j>s} wm_j max(G_s, R_{s+1,j}) for each movable
            # toggle position s = ps[..., col]: one scan over queue
            # positions with an O(B S) carry and five small fused ops per
            # step — no O(n^2) tensors, and the carry width is the
            # MOVABLE count, not the instance size. For j <= s the
            # unmasked accumulator collects wm_j G_s (R is still -inf
            # there), subtracted afterwards via wpre.
            Gm = gat(G)

            def step(carry, xs):
                R, acc = carry                         # (B, 2, S) each
                j, q_j, wm_j = xs                      # scalar, (B,2) x2
                R = jnp.maximum(
                    R, jnp.where(j > ps, q_j[..., None], -jnp.inf))
                acc = acc + wm_j[..., None] * jnp.maximum(Gm, R)
                return (R, acc), None

            init = (jnp.full((B, 2, S), -jnp.inf),
                    jnp.zeros((B, 2, S), p_T.dtype))
            (_, accT), _ = jax.lax.scan(
                step, init, (jnp.arange(n), lead(q), lead(wm)), unroll=4)
            tog = gat(pre) + gat(own) + (accT - Gm * gat(wpre)) \
                + gat(suf_lin)
            return stat, tog

        # "last" objective: the same toggle decomposition holds under max
        # (DESIGN.md §12) — members before s keep their incumbent
        # completions, an inserted s completes at G_s + csum_s, and for
        # members j > s the max of e'_j = max(G_s, R_{s+1,j}) + C_j
        # splits into G_s + max_j C_j plus the max-plus exchange
        #   max_{j>s}(R_{s+1,j} + C_j) = max_{i>s}(q_i + SC_i),
        # SC = inclusive suffix cummax of member csum — all O(n)
        # prefix/suffix cummaxes, no sequential walk (ROADMAP
        # accelerator-truth item).
        neg = jnp.full((B, 2, 1), -jnp.inf)
        e_mem = jnp.where(mask_T, e_inc, -jnp.inf)
        pmax = jnp.concatenate(
            [neg, jax.lax.cummax(e_mem, axis=2)[:, :, :-1]], 2)
        csum_mem = jnp.where(mask_T, csum, -jnp.inf)
        SC = jnp.flip(jax.lax.cummax(jnp.flip(csum_mem, 2), axis=2), 2)
        SCx = jnp.concatenate([SC[:, :, 1:], neg], 2)
        g = q + SC
        Hx = jnp.concatenate(
            [jnp.flip(jax.lax.cummax(jnp.flip(g, 2), axis=2),
                      2)[:, :, 1:], neg], 2)
        own = jnp.where(mask_T, -jnp.inf, G + csum)
        tog = jnp.maximum(jnp.maximum(pmax, own),
                          jnp.maximum(G + SCx, Hx))
        tog = jnp.maximum(tog, 0.0)            # empty-queue floor
        stat = jnp.maximum(
            jnp.max(e_mem, axis=2, initial=-jnp.inf), 0.0)
        return stat, gat(tog)

    slots = jnp.arange(m)
    # column S is a sentinel toggle position (n, never a queue index):
    # its row walks the untouched incumbent with identical arithmetic
    ps_ext = jnp.concatenate(
        [ps, jnp.full((B, 2, 1), n, ps.dtype)], axis=2)

    def step(carry, xs):
        free, acc = carry                   # (B, 2, S+1, m), (B, 2, S+1)
        j, a_j, p_j, w_j, rel_j, m_j = xs   # scalar, then (B, 2) each
        live = m_j[..., None] != (j == ps_ext)
        slot = jnp.argmin(free, axis=3)
        fmin = jnp.take_along_axis(free, slot[..., None], axis=3)[..., 0]
        e = jnp.maximum(a_j[..., None], fmin) + p_j[..., None]
        free = jnp.where((slots == slot[..., None]) & live[..., None],
                         e[..., None], free)
        if oi == 2:
            acc = jnp.maximum(acc, jnp.where(live, e, 0.0))
        else:
            resp = e - rel_j[..., None]
            acc = acc + jnp.where(
                live, w_j[..., None] * resp if oi == 0 else resp, 0.0)
        return (free, acc), None

    init = (jnp.broadcast_to(busy_T[:, :, None, :], (B, 2, S + 1, m)),
            jnp.zeros((B, 2, S + 1), p_T.dtype))
    (_, acc), _ = jax.lax.scan(
        step, init, (jnp.arange(n), lead(arr_T), lead(p_T), lead(w_T),
                     lead(rel_T), lead(mask_T)))
    return acc[:, :, S], acc[:, :, :S]


def _device_round(assign, dev_end, dev_resp, dev_wresp, oi: int):
    """Incumbent + toggled stats of the private device tier, O(B n):
    per-job contributions are constants, so sum objectives are one ± of a
    precomputed constant and "last" needs only the masked top-2."""
    member = assign == 2
    if oi == 2:
        iota = jnp.arange(assign.shape[1])
        ends = jnp.where(member, dev_end, -jnp.inf)
        amax = jnp.argmax(ends, axis=1)
        max1 = jnp.take_along_axis(ends, amax[:, None], axis=1)[:, 0]
        is_max = iota == amax[:, None]
        max2 = jnp.max(jnp.where(is_max, -jnp.inf, ends), axis=1,
                       initial=-jnp.inf)
        stat = jnp.maximum(max1, 0.0)
        tog = jnp.where(
            member,
            jnp.maximum(jnp.where(is_max, max2[:, None], max1[:, None]),
                        0.0),
            jnp.maximum(stat[:, None], dev_end))
        return stat, tog
    con = dev_wresp if oi == 0 else dev_resp
    stat = jnp.sum(jnp.where(member, con, 0.0), axis=1)
    return stat, stat[:, None] + jnp.where(member, -con, con)


def _round_batched(assign, mov_idx, mov_ok, tc, dev, oi: int):
    """One delta-evaluated neighbourhood round for the whole batch.

    Returns ((B,) incumbent objectives, (B, S, 3) candidate values):
    entry (b, i, m) is the exact objective of instance b with job
    mov_idx[b, i] moved to machine m, assembled from the two affected
    tiers' toggled stats and the incumbent's third-tier stat. Only
    movable jobs get candidate slots (DESIGN.md §12) — phantom padding,
    frozen background jobs, and interval reservations participate fully
    in every queue evaluation (they occupy machines and count toward the
    objective) but never appear in mov_idx, so a mostly-frozen ward
    prices O(movable) candidates per round. No-op moves and invalid
    padding slots (~mov_ok) score +inf. tc holds the stacked (B, 2, n)
    per-tier queue-order constants; dev the device-tier constants."""
    B, n = assign.shape
    S = mov_idx.shape[1]
    mask_T = jnp.take_along_axis(
        jnp.stack([assign == 0, assign == 1], axis=1), tc["order"], axis=2)
    # queue positions of the movable jobs on each tier — tog comes back
    # already aligned with the movable slots, no pos->job scatter needed
    ps = jnp.take_along_axis(
        tc["pos"], jnp.broadcast_to(mov_idx[:, None, :], (B, 2, S)), axis=2)
    stat_T, tog_T = _tier_rounds(mask_T, tc["arr"], tc["p"], tc["w"],
                                 tc["rel"], tc["busy"], ps, oi)
    stat_d, tog_d = _device_round(assign, dev["end"], dev["resp"],
                                  dev["wresp"], oi)
    tog_d = jnp.take_along_axis(tog_d, mov_idx, axis=1)      # (B, S)
    a_mov = jnp.take_along_axis(assign, mov_idx, axis=1)     # (B, S)
    stats = jnp.concatenate([stat_T, stat_d[:, None]], 1)    # (B, 3)
    tog = jnp.concatenate([tog_T, tog_d[:, None, :]], 1)     # (B, 3, S)
    if oi == 2:
        total = jnp.max(stats, axis=1)
        src_t = jnp.take_along_axis(tog, a_mov[:, None, :],
                                    axis=1)[:, 0, :]
        third = jnp.clip(
            3 - a_mov[:, :, None] - jnp.arange(3)[None, None, :], 0, 2)
        stats_third = jnp.take_along_axis(
            stats, third.reshape(B, -1), axis=1).reshape(B, S, 3)
        vals = jnp.maximum(jnp.maximum(src_t[:, :, None],
                                       tog.transpose(0, 2, 1)),
                           stats_third)
    else:
        total = stats[:, 0] + stats[:, 1] + stats[:, 2]
        d = tog - stats[:, :, None]             # per-tier toggle deltas
        src_d = jnp.take_along_axis(d, a_mov[:, None, :], axis=1)[:, 0, :]
        vals = total[:, None, None] + src_d[:, :, None] + \
            d.transpose(0, 2, 1)
    vals = jnp.where(jnp.arange(3)[None, None, :] == a_mov[:, :, None],
                     jnp.inf, vals)
    vals = jnp.where(mov_ok[:, :, None], vals, jnp.inf)
    return total, vals


def _greedy_assign_batched(rel, w, proc, trans, valid, busy_c, busy_e):
    """Vectorised `scheduler.greedy_schedule` for the whole batch: jobs in
    (release, -weight, index) order, each to the machine minimising its
    completion time given the free slots so far, ties to the lower tier
    (device < edge < cloud) — the same rule, same tie-breaks. One lax.scan
    over job ranks runs every instance in lockstep; phantom jobs are
    skipped and stay pinned to the (zero-cost) device tier."""
    B, n = rel.shape
    order = jax.vmap(lambda r, ww: jnp.lexsort((-ww, r)))(rel, w)
    binds = jnp.arange(B)

    m_mm = max(busy_c.shape[1], busy_e.shape[1])
    free_T0 = jnp.stack([                            # (B, 2, m), +inf pads
        jnp.pad(busy_c, ((0, 0), (0, m_mm - busy_c.shape[1])),
                constant_values=jnp.inf),
        jnp.pad(busy_e, ((0, 0), (0, m_mm - busy_e.shape[1])),
                constant_values=jnp.inf)], axis=1)
    slots = jnp.arange(m_mm)

    def step(carry, j):
        free_T, assign = carry                       # (B, 2, m), (B, n)
        k = order[:, j]                              # (B,) this rank's job
        v = valid[binds, k]
        r = rel[binds, k]
        arr_T = r[:, None] + trans[binds, k, :2]     # (B, 2)
        slot = jnp.argmin(free_T, axis=2)            # earliest-free machine
        fmin = jnp.take_along_axis(free_T, slot[..., None], axis=2)[..., 0]
        end_T = jnp.maximum(arr_T, fmin) + proc[binds, k, :2]
        end_dev = r + trans[binds, k, 2] + proc[binds, k, 2]
        # argmin over [device, edge, cloud] keeps the first (lowest) tier
        # on ties, exactly like greedy_schedule's (ED, ES, CC) probe order
        pick = jnp.argmin(
            jnp.stack([end_dev, end_T[:, 1], end_T[:, 0]], 1), axis=1)
        tier = jnp.asarray([2, 1, 0], jnp.int32)[pick]
        assign = assign.at[binds, k].set(
            jnp.where(v, tier, assign[binds, k]))
        claim = (v[:, None] & (tier[:, None] == jnp.arange(2)))[..., None] \
            & (slots == slot[..., None])
        free_T = jnp.where(claim, end_T[..., None], free_T)
        return (free_T, assign), None

    init = (free_T0, jnp.full((B, n), 2, jnp.int32))
    (_, assign), _ = jax.lax.scan(step, init, jnp.arange(n))
    return assign


def _run_rounds(assign0, mov_idx, mov_ok, tc, dev, oi, max_moves, binds):
    """mode="round" inner loop (see `_tabu_run_batched`): steepest
    descent over the S x 3 single-move neighbourhood, one wide
    delta-evaluated round per while_loop iteration, accept each
    instance's best strictly improving move plus a second,
    exactly-composing move on the other shared tier when one improves
    (cloud/edge queues are disjoint and the private device tier is
    additive per job, so the pair composes exactly for sum
    objectives)."""
    B, _ = assign0.shape
    S = mov_idx.shape[1]

    def round_all(assign):
        return _round_batched(assign, mov_idx, mov_ok, tc, dev, oi)

    def cond(state):
        _, _, rnd, active = state
        return jnp.any(active) & (rnd < max_moves)

    def body(state):
        assign, _, rnd, active = state
        total, vals = round_all(assign)
        flat = vals.reshape(B, -1)              # candidate (s, m) = s*3+m
        i1 = jnp.argmin(flat, axis=1)
        v1 = jnp.take_along_axis(flat, i1[:, None], axis=1)[:, 0]
        s1 = i1 // N_MACHINES
        k1 = jnp.take_along_axis(mov_idx, s1[:, None], axis=1)[:, 0]
        m1 = (i1 % N_MACHINES).astype(assign.dtype)
        improved = active & (v1 < total)
        src1 = assign[binds, k1]
        new_assign = assign.at[binds, k1].set(
            jnp.where(improved, m1, src1))
        # the carried value is the FRESH per-tier evaluation of the
        # incumbent whenever a ward converges (its last round rejects
        # every move, so `total` is its final assignment's exact score);
        # only a max_rounds cap can surface a delta-assembled value
        value = jnp.where(improved, v1, total)
        if oi != 2:
            # paired acceptance: a second strictly-improving move whose
            # shared-tier footprint is disjoint from the first composes
            # EXACTLY for sum objectives — its standalone delta still
            # holds after the first move commits
            sh0 = (src1 == 0) | (m1 == 0)
            sh1 = (src1 == 1) | (m1 == 1)
            other = jnp.where(sh0, 1, 0).astype(assign.dtype)
            pairable = improved & ~(sh0 & sh1)
            a_slot = jnp.take_along_axis(assign, mov_idx, axis=1)
            ok_src = (a_slot == other[:, None]) | (a_slot == 2)
            mr = jnp.arange(N_MACHINES)[None, None, :]
            ok_dst = (mr == other[:, None, None]) | (mr == 2)
            elig = (ok_src[:, :, None] & ok_dst &
                    (jnp.arange(S)[None, :, None] != s1[:, None, None]))
            flat2 = jnp.where(elig.reshape(B, -1), flat, jnp.inf)
            i2 = jnp.argmin(flat2, axis=1)
            v2 = jnp.take_along_axis(flat2, i2[:, None], axis=1)[:, 0]
            s2 = i2 // N_MACHINES
            k2 = jnp.take_along_axis(mov_idx, s2[:, None], axis=1)[:, 0]
            m2 = (i2 % N_MACHINES).astype(assign.dtype)
            accept2 = pairable & (v2 < total)
            new_assign = new_assign.at[binds, k2].set(
                jnp.where(accept2, m2, new_assign[binds, k2]))
            value = jnp.where(accept2, value + (v2 - total), value)
        return new_assign, value, rnd + 1, improved

    state = (assign0, jnp.full((B,), jnp.inf), jnp.int32(0),
             jnp.ones((B,), bool))
    assign, totals, rounds, _ = jax.lax.while_loop(cond, body, state)
    # max_rounds == 0 (greedy probe): the loop never evaluated anything
    totals = jax.lax.cond(rounds == 0,
                          lambda args: round_all(args[0])[0],
                          lambda args: args[1], (assign, totals))
    return assign, totals, rounds


def _kernel_consts(rel, w, proc, trans, busy_c, busy_e):
    """The assignment-independent constants every evaluation of a search
    reads: per shared tier the queue order and the job constants in it,
    stacked (B, 2, n), with the (B, 2, m) machine free times (the smaller
    tier padded with +inf phantom machines); and the private device
    tier's per-job completion and response constants."""
    m_mm = max(busy_c.shape[1], busy_e.shape[1])
    busy_T = jnp.stack([
        jnp.pad(busy_c, ((0, 0), (0, m_mm - busy_c.shape[1])),
                constant_values=jnp.inf),
        jnp.pad(busy_e, ((0, 0), (0, m_mm - busy_e.shape[1])),
                constant_values=jnp.inf)], axis=1)           # (B, 2, m)
    parts = []
    for m in (0, 1):
        arr = rel + trans[:, :, m]
        order = jax.vmap(lambda r, a: jnp.lexsort((r, a)))(rel, arr)
        pos = jax.vmap(jnp.argsort)(order)      # job id -> queue position

        def gat(x, o=order):
            return jnp.take_along_axis(x, o, axis=1)

        parts.append({"order": order, "pos": pos, "arr": gat(arr),
                      "p": gat(proc[:, :, m]), "w": gat(w),
                      "rel": gat(rel)})
    tc = {key: jnp.stack([parts[0][key], parts[1][key]], axis=1)
          for key in parts[0]}                  # each (B, 2, n)
    tc["busy"] = busy_T
    dev_end = rel + trans[:, :, 2] + proc[:, :, 2]
    dev = {"end": dev_end, "resp": dev_end - rel,
           "wresp": w * (dev_end - rel)}
    return tc, dev


def _pass_walk(assign, active, mov_idx, mov_ok, tc, dev, oi: int):
    """One mode="pass" pass for a batch of several wards: S width-1
    evaluations in slot order, each pricing one slot's 3 moves against
    the assignment as of that slot and committing a strictly improving
    best move at once. Returns (assign, value, changed, evaluations)."""
    B, S = mov_idx.shape
    binds = jnp.arange(B)

    def slot(carry, s):
        assign, total, changed = carry
        k = jnp.take(mov_idx, s, axis=1)            # (B,) job id
        ok = jnp.take(mov_ok, s, axis=1) & active
        tot, vals = _round_batched(assign, k[:, None], ok[:, None],
                                   tc, dev, oi)
        flat = vals[:, 0, :]                        # (B, 3)
        m1 = jnp.argmin(flat, axis=1)
        v1 = jnp.take_along_axis(flat, m1[:, None], axis=1)[:, 0]
        improved = v1 < tot         # +inf masks no-ops and ~ok slots
        assign = assign.at[binds, k].set(
            jnp.where(improved, m1.astype(assign.dtype), assign[binds, k]))
        # the carried value is the FRESH per-tier evaluation of the
        # incumbent whenever the slot rejects its moves — so a converged
        # ward (a full pass of rejections) always reports its final
        # assignment's exact score; only a max_rounds cap can surface a
        # (one-composition) delta-assembled value
        total = jnp.where(improved, v1, tot)
        return (assign, total, changed | improved), None

    (assign, total, changed), _ = jax.lax.scan(
        slot, (assign, jnp.full((B,), jnp.inf), jnp.zeros((B,), bool)),
        jnp.arange(S))
    return assign, total, changed, jnp.int32(S)


def _pass_replay(assign, active, mov_idx, mov_ok, tc, dev, oi: int):
    """One mode="pass" pass for a single ward (the kernel takes it at
    B = 1; the code holds for any B): the same accept-as-you-go walk as
    `_pass_walk`, replayed with one wide evaluation per accepted move.
    Each instance carries a slot cursor; an evaluation prices every slot
    at or past it against the current assignment, the first slot whose
    best move strictly improves commits and the cursor moves past it,
    and a cursor that finds none ends the instance's pass. Returns
    (assign, value, changed, evaluations)."""
    B, S = mov_idx.shape
    binds = jnp.arange(B)
    slots = jnp.arange(S)

    def more(carry):
        return jnp.any(carry[2] < S)

    def step(carry):
        assign, total, cur, changed, evals = carry
        # the slots a width-1 walk would visit before its next accept all
        # see exactly this assignment
        tot, vals = _round_batched(
            assign, mov_idx, mov_ok & active[:, None]
            & (slots >= cur[:, None]), tc, dev, oi)
        m1 = jnp.argmin(vals, axis=2)                       # (B, S)
        v1 = jnp.take_along_axis(vals, m1[..., None], axis=2)[..., 0]
        better = v1 < tot[:, None]          # +inf masks no-ops, ~ok slots
        improved = jnp.any(better, axis=1)
        s1 = jnp.argmax(better, axis=1)                     # the first
        k = mov_idx[binds, s1]
        assign = assign.at[binds, k].set(
            jnp.where(improved, m1[binds, s1].astype(assign.dtype),
                      assign[binds, k]))
        # a ward whose cursor reached S keeps its pass's value; the others
        # take the walk's: the accepted move's, or the FRESH incumbent when
        # no slot past the cursor improves
        total = jnp.where(cur < S,
                          jnp.where(improved, v1[binds, s1], tot), total)
        cur = jnp.where(improved, s1 + 1, S)
        return assign, total, cur, changed | improved, evals + 1

    assign, total, _, changed, evals = jax.lax.while_loop(
        more, step, (assign, jnp.full((B,), jnp.inf),
                     jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
                     jnp.int32(0)))
    return assign, total, changed, evals


def _run_passes(assign0, mov_idx, mov_ok, tc, dev, oi, max_rounds,
                one_pass):
    """mode="pass" outer loop (see `_tabu_run_batched`): `one_pass`
    (`_pass_walk` or `_pass_replay`) per while_loop iteration until no
    ward changed or `max_rounds` passes ran. Returns (assign, totals,
    rounds, evaluations)."""
    B = assign0.shape[0]

    def cond(state):
        _, _, rnd, active, _ = state
        return jnp.any(active) & (rnd < max_rounds)

    def body(state):
        assign, _, rnd, active, evals = state
        assign, total, changed, ran = one_pass(assign, active, mov_idx,
                                               mov_ok, tc, dev, oi)
        return assign, total, rnd + 1, changed, evals + ran

    state = (assign0, jnp.full((B,), jnp.inf), jnp.int32(0),
             jnp.ones((B,), bool), jnp.int32(0))
    assign, totals, rounds, _, evals = jax.lax.while_loop(cond, body, state)
    # max_rounds == 0 (greedy probe): the loop never evaluated anything
    totals = jax.lax.cond(
        rounds == 0,
        lambda args: _round_batched(args[0], mov_idx, mov_ok, tc, dev,
                                    oi)[0],
        lambda args: args[1], (assign, totals))
    return assign, totals, rounds, evals + (rounds == 0)


@functools.partial(jax.jit,
                   static_argnames=("objective", "greedy_init", "mode"))
def _tabu_run_batched(assign0, rel, w, proc, trans, movable, mov_idx,
                      mov_ok, max_rounds, busy_c, busy_e, objective: str,
                      greedy_init: bool = False, mode: str = "pass"):
    """Algorithm-2 search for B instances at once, entirely on-device,
    in one of two shape-dispatched regimes (DESIGN.md §12):

    mode="pass" — the mostly-background regime (movable slots are a
    small fraction of the padded rows). Each while_loop iteration is
    one PASS over the movable slots in slot order, accepting as it goes
    like the incremental Python tabu round: a slot's best move (argmin
    over the 3 machines) commits when it strictly beats the assignment
    as of that slot. A single ward (B = 1: every replan) replays that
    first-improvement walk with one wide evaluation per accepted move
    plus one per pass (`_pass_replay`): every slot the walk would price
    before its next accept sees the same assignment, and per toggle
    column the tier arithmetic is elementwise-identical at any width, so
    assignments and values come out bit for bit as the walk's. On the
    chip an evaluation costs its count of sequential ops, not its carry
    width (a (1, 2, S, m) carry is a fraction of one vreg), so skipping
    the evaluations whose answer is already known is the saving. A
    batch of several wards keeps the walk of S width-1 evaluations
    (`_pass_walk`): fleet sweeps (S ~ 112 slots over ~3,100 rows) run it
    on the CPU too, where a wide evaluation's carry costs in proportion
    to its width and the replay ran the benchmark fleet 3.5x slower.

    mode="round" — the movable-dominated regime. One steepest-descent
    round per while_loop iteration: all S toggles priced in one wide
    carry, accept each instance's best strictly improving move (plus a
    second, exactly-composing move on the other shared tier when one
    improves). At small row counts the per-eval dispatch floor — not
    carry width — dominates, so one wide eval per accepted move beats
    S narrow evals per pass; `max_rounds` passes translate to a
    `max_rounds * S` move budget.

    Both regimes share the tier/device precomputation, per-instance
    convergence flags (a converged ward idles while stragglers keep
    searching), and drift-free values: the incumbent objective is
    re-derived from fresh per-tier stats at every evaluation, so a
    converged ward's reported value is a fresh full evaluation.
    Machine counts are carried by the busy vector shapes (phantom
    machines = +inf), so changing fleet sizes does not retrace beyond
    the new shapes. max_rounds counts passes (the Python search's
    max_count). Returns (assign, totals, rounds, evaluations): the last
    is the number of tier evaluations the batch ran, the greedy probe's
    included."""
    oi = _OBJ_IDX[objective]
    B = assign0.shape[0]
    if greedy_init:
        # greedy init is only reachable when every non-phantom job is
        # movable (frozen jobs require an explicit initial assignment)
        assign0 = _greedy_assign_batched(rel, w, proc, trans, movable,
                                         busy_c, busy_e)
    tc, dev = _kernel_consts(rel, w, proc, trans, busy_c, busy_e)
    binds = jnp.arange(B)
    S = mov_idx.shape[1]
    # real (non-padding) slots are a per-ward PREFIX of mov_idx
    # (_movable_slots packs them first), so slot s of pass r visits the
    # same job for a ward no matter how much batch padding it rides with
    # (the batched==solo parity suite pins this)
    if mode == "round":
        assign, totals, rounds = _run_rounds(
            assign0, mov_idx, mov_ok, tc, dev, oi,
            max_rounds * jnp.int32(S), binds)
        return assign, totals, rounds, rounds + (rounds == 0)
    return _run_passes(assign0, mov_idx, mov_ok, tc, dev, oi, max_rounds,
                       _pass_replay if B == 1 else _pass_walk)


def _reservation_rows(resv):
    """Host-side kernel rows for one ward's {tier: [Reservation]} map
    (DESIGN.md §12) — the interval representation compiles into ordinary
    pinned rows appended AFTER the instance's jobs: arrival enters via
    trans = arrival − release (so queue key (arrival, release, index)
    ties break jobs-first, then reservation input order, exactly like
    `simulate`), the row occupies its tier's pool for ``proc`` and
    contributes weight*(end − release) to the objective, and movable
    stays False so no round ever prices a move on it. Returns the
    (K, 8) _specs_to_np-layout block plus the (K,) tier codes."""
    rows, tiers = [], []
    for m, tier in ((0, CC), (1, ES)):
        for r in (resv or {}).get(tier, ()):
            p = [0.0] * N_MACHINES
            t = [0.0] * N_MACHINES
            p[m] = float(r.proc)
            t[m] = float(r.arrival) - float(r.release)
            rows.append((float(r.release), float(r.weight), *p, *t))
            tiers.append(m)
    bad = sorted(set(resv or {}) - {CC, ES})
    if bad:
        raise ValueError(f"reservations may only name shared tiers "
                         f"[{CC!r}, {ES!r}], got {bad}")
    return (np.asarray(rows, np.float32).reshape(-1, 8),
            np.asarray(tiers, np.int32))


def _slot_bucket(smax: int, n_max: int) -> int:
    """The movable-slot count S of a batch whose largest instance has
    `smax` movable jobs (DESIGN.md §12): rounded up to a multiple of 16
    (capped at n_max), so the compiled (B, n, S) kernel shape stays
    stable while reservation/background counts drift under metro load."""
    return min(n_max, ((max(smax, 1) + 15) // 16) * 16)


def _movable_slots(movable: np.ndarray, mov_idx: np.ndarray,
                   mov_ok: np.ndarray) -> None:
    """Fill the (B, S) movable-slot arrays from the (B, n) movable mask:
    each ward's movable job ids as a prefix of its row, mov_ok 1 there;
    padding slots point at job 0 with mov_ok 0 and are masked +inf by
    the round."""
    for b in range(movable.shape[0]):
        idx = np.flatnonzero(movable[b])
        mov_idx[b, :len(idx)] = idx
        mov_ok[b, :len(idx)] = True


# The packed calling convention of the device search (DESIGN.md §8):
# `_tabu_run_batched`'s eleven inputs cross to the device as one flat
# int32 buffer, fields in this order, float32 bit-cast ("f"), bools as
# 0/1 ("b"), int32 as is ("i"); the result comes back as one array.
_PACKED_FIELDS = (("assign0", "i"), ("rel", "f"), ("w", "f"),
                  ("proc", "f"), ("trans", "f"), ("movable", "b"),
                  ("mov_idx", "i"), ("mov_ok", "b"), ("max_rounds", "i"),
                  ("busy_c", "f"), ("busy_e", "f"))


@functools.lru_cache(maxsize=64)
def _packed_fields(layout: Tuple[int, int, int, int, int]):
    """((name, kind, shape, offset, size), ...) of each packed field for
    the static layout (B, n_max, S, m_cloud, m_edge), and the buffer's
    length. Cached: every search packs, and a run meets only the few
    layouts it compiled."""
    B, n, S, mc, me = layout
    shapes = {"assign0": (B, n), "rel": (B, n), "w": (B, n),
              "proc": (B, n, N_MACHINES), "trans": (B, n, N_MACHINES),
              "movable": (B, n), "mov_idx": (B, S), "mov_ok": (B, S),
              "max_rounds": (), "busy_c": (B, mc), "busy_e": (B, me)}
    fields, off = [], 0
    for name, kind in _PACKED_FIELDS:
        size = math.prod(shapes[name])
        fields.append((name, kind, shapes[name], off, size))
        off += size
    return tuple(fields), off


def _field_views(buf: np.ndarray, layout):
    """A writable host view of each field into the packed int32 buffer
    (float32 fields as float32 views, the rest as int32), so filling the
    fields packs them."""
    views = {}
    for name, kind, shape, off, size in _packed_fields(layout)[0]:
        v = buf[off:off + size].reshape(shape)
        views[name] = v.view(np.float32) if kind == "f" else v
    return views


def _unpack(buf, layout):
    """`_tabu_run_batched`'s eleven inputs, in its argument order, from
    the packed buffer, traced: static slices, float32 bit-cast back,
    bools as != 0. Every value comes back bit for bit."""
    fields, _ = _packed_fields(layout)
    out = []
    for _, kind, shape, off, size in fields:
        x = buf[off:off + size].reshape(shape)
        if kind == "f":
            x = jax.lax.bitcast_convert_type(x, jnp.float32)
        elif kind == "b":
            x = x != 0
        out.append(x)
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("layout", "objective",
                                             "greedy_init", "mode"))
def _tabu_run_packed(buf, layout, objective: str,
                     greedy_init: bool = False, mode: str = "pass"):
    """`_tabu_run_batched` across one buffer each way: the inputs from the
    packed buffer (`_unpack`), the result as one (B, n_max + 2) int32
    array, the assignment in the first n_max columns, the float32
    objective bit-cast into the next and the batch's tier-evaluation
    count in the last. The round count stays on the device."""
    assign, totals, _, evals = _tabu_run_batched(
        *_unpack(buf, layout), objective, greedy_init=greedy_init,
        mode=mode)
    return jnp.concatenate(
        [assign, jax.lax.bitcast_convert_type(totals, jnp.int32)[:, None],
         jnp.broadcast_to(evals, (assign.shape[0], 1))], axis=1)


def _per_instance_mpt(machines_per_tier, B: int):
    """-> B (cloud, edge) machine-count pairs from one pair or a per-ward
    sequence."""
    if machines_per_tier is None:
        return [(1, 1)] * B
    seq = list(machines_per_tier)
    if len(seq) == 2 and all(isinstance(x, (int, np.integer)) for x in seq):
        return [(int(seq[0]), int(seq[1]))] * B
    if len(seq) != B:
        raise ValueError(f"machines_per_tier lists {len(seq)} fleets "
                         f"for {B} instances")
    return [(int(c), int(e)) for c, e in seq]


def kernel_regime(slots: int, rows: int) -> str:
    """The batched search's regime for S movable slots in a batch padded
    to `rows` rows: "round" when the movable slots fill at least half the
    rows, "pass" when background dominates (DESIGN.md §12)."""
    return "round" if 2 * slots >= rows else "pass"


def tabu_search_batched(batch_jobs: Sequence[Sequence[JobSpec]],
                        initial: Sequence[Sequence[int]] | None = None,
                        *, max_rounds: int | None = None,
                        objective: str = "weighted",
                        machines_per_tier=(1, 1),
                        busy_until=None,
                        frozen=None,
                        reserved=None,
                        pad_to: int | None = None):
    """Plan B independent ward instances in ONE jitted device call.

    batch_jobs: B job lists; sizes may differ — instances are padded to
    the largest with phantom jobs (p = 0, w = 0, masked transparent:
    arr = −inf in every shared queue) that contribute exactly 0 to every
    objective and whose moves score +inf. machines_per_tier: one
    (cloud, edge) pair for the whole fleet or a per-ward sequence; mixed
    fleets are padded to the per-tier maximum with phantom machines whose
    initial busy time is +inf, so FIFO dispatch never selects them.
    busy_until: optional per-ward (cloud_times, edge_times) pairs.

    frozen: optional per-ward boolean masks (DESIGN.md §9). A frozen job
    participates FULLY in every queue evaluation — it occupies its
    machine pool and its response counts toward the objective — but every
    move on it scores +inf, so the search can never reassign it. This is
    how the fleet fixed-point solver shows ward b the other wards'
    committed shared-tier jobs as background occupancy. Frozen jobs
    require an explicit ``initial`` (the greedy initialiser would
    reassign them). pad_to: pad instances to at least this many job slots
    — contention sweeps bucket their background size with it so the
    compiled shape stays stable while the background churns.

    reserved: optional per-ward {tier: [Reservation]} maps (DESIGN.md
    §12) — committed background occupancy on the shared tiers. Each
    reservation compiles into one pinned row appended after the ward's
    jobs (occupies its pool, counts toward the objective, never movable),
    but because the toggle carry only tracks MOVABLE slots, reservations
    cost O(1) carry width instead of widening the O(n) candidate set the
    way frozen phantom jobs did. Requires an explicit ``initial`` (for
    the ward's own jobs only — reservation rows pin themselves).

    Returns (objectives (B,) float ndarray, [per-ward (n_i,) int arrays])
    where objectives INCLUDE reservation contributions and assignments
    cover only the ward's own jobs. Termination is per-instance: a ward
    that reaches a 1-move local optimum goes inactive while stragglers
    keep searching; the device call returns when every ward has converged
    (or after max_rounds accept-as-you-go passes over the movable slots —
    the Python search's max_count, default 50). Each ward's
    trajectory is identical to a solo `tabu_search_jax` run — same pass
    code, same tie-breaks — which the parity suite pins (DESIGN.md §8).
    Recompiles per (B, n_max, movable bucket S, padded machine counts,
    objective); replans reusing one shape hit the cache.
    """
    B = len(batch_jobs)
    if B == 0:
        return np.zeros((0,)), []
    with spans.span("scheduler.pack"):
        if reserved is None:
            reserved = [None] * B
        elif initial is None and any(r for r in reserved):
            raise ValueError("reservations require an explicit initial "
                             "assignment (greedy init ignores their "
                             "occupancy)")
        rsv = [_reservation_rows(r) for r in reserved]
        sizes = [len(jobs) for jobs in batch_jobs]
        rows = [nb + rr.shape[0] for nb, (rr, _) in zip(sizes, rsv)]
        n_max = max(rows)
        if pad_to is not None:
            n_max = max(n_max, int(pad_to))
        if frozen is not None and initial is None:
            raise ValueError("frozen jobs require an explicit initial "
                             "assignment (greedy init would reassign them)")
        mpts = _per_instance_mpt(machines_per_tier, B)
        m_max = (max(c for c, _ in mpts), max(e for _, e in mpts))
        if busy_until is None:
            busy_until = [None] * B
        if n_max == 0:
            return np.zeros((B,)), [np.zeros((0,), np.int64) for _ in range(B)]
        # the frozen masks first: they fix the movable-slot bucket S, and
        # so the buffer's layout
        frozen_masks = [None] * B
        if frozen is not None:
            for b in range(B):
                if sizes[b] and frozen[b] is not None:
                    fr = np.asarray(list(frozen[b]), bool)
                    if fr.shape != (sizes[b],):
                        raise ValueError(
                            f"ward {b}: frozen mask has shape {fr.shape}, "
                            f"expected ({sizes[b]},)")
                    frozen_masks[b] = fr
        smax = max(nb - (0 if fr is None else int(fr.sum()))
                   for nb, fr in zip(sizes, frozen_masks))
        layout = (B, n_max, _slot_bucket(smax, n_max), *m_max)
        # every field is a view into the one buffer the dispatch moves
        buf = np.zeros(_packed_fields(layout)[1], np.int32)
        f = _field_views(buf, layout)
        f["assign0"][:] = 2                         # phantoms pinned to device
        f["busy_c"][:] = np.inf
        f["busy_e"][:] = np.inf
        for b, jobs in enumerate(batch_jobs):
            nb = sizes[b]
            bc, be = _normalize_busy(busy_until[b], mpts[b])
            f["busy_c"][b, :mpts[b][0]] = bc
            f["busy_e"][b, :mpts[b][1]] = be
            rr, rt = rsv[b]
            if nb:
                (f["rel"][b, :nb], f["w"][b, :nb], f["proc"][b, :nb],
                 f["trans"][b, :nb]) = _specs_to_np(jobs)
                f["movable"][b, :nb] = True if frozen_masks[b] is None \
                    else ~frozen_masks[b]
                if initial is not None:
                    f["assign0"][b, :nb] = list(initial[b])
            if rt.shape[0]:
                hi = nb + rt.shape[0]
                f["rel"][b, nb:hi] = rr[:, 0]
                f["w"][b, nb:hi] = rr[:, 1]
                f["proc"][b, nb:hi] = rr[:, 2:5]
                f["trans"][b, nb:hi] = rr[:, 5:8]
                f["assign0"][b, nb:hi] = rt
        _movable_slots(f["movable"], f["mov_idx"], f["mov_ok"])
        f["max_rounds"][...] = 50 if max_rounds is None else max_rounds
        # static regime dispatch (DESIGN.md §12): movable-dominated batches
        # (movable bucket at least half the padded rows) take the wide
        # steepest-descent rounds; background-heavy batches take the
        # accept-as-you-go movable-slot passes. Both sides of the
        # threshold are a pure function of the batch's padded shape, so
        # every ward of one call follows one regime and B = 1 replays it
        # exactly.
        mode = kernel_regime(layout[2], n_max)
    # what the dispatch moves and runs, all known on the host; counted
    # only while a recorder is armed
    armed = spans.armed() is not None
    counters = {} if not armed else dict(
        B=B, rows_real=sum(rows), rows_padded=B * n_max, slots=layout[2],
        reserved_rows=sum(rr.shape[0] for rr, _ in rsv),
        regime=mode, h2d_arrays=1, h2d_bytes=int(buf.nbytes))
    with spans.span("scheduler.dispatch", **counters):
        out = _tabu_run_packed(buf, layout, objective,
                               greedy_init=initial is None, mode=mode)
    with spans.span("scheduler.fetch", **({"d2h_arrays": 1} if armed
                                          else {})) as fetch:
        out = np.asarray(out)
        if armed and mode == "pass":
            fetch.set(pass_evals=int(out[0, n_max + 1]))
    totals = out[:, n_max].view(np.float32).astype(np.float64)
    return totals, [out[b, :sizes[b]] for b in range(B)]


def tabu_search_jax(jobs: Sequence[JobSpec],
                    initial: Sequence[int] | np.ndarray | None = None,
                    *, max_rounds: int | None = None,
                    objective: str = "weighted",
                    machines_per_tier: Tuple[int, int] = (1, 1),
                    busy_until=None, frozen=None, reserved=None):
    """Fully-jitted Algorithm-2 neighbourhood search. Returns
    (best objective value, best assignment as an (n,) int array).

    The whole search — delta-evaluated n x 3 neighbourhood rounds, move
    acceptance, termination — runs inside one jitted lax.while_loop; the
    only transfer is the final result. Each accepted move strictly
    improves the objective, so the search terminates at a 1-move local
    optimum of the same neighbourhood the Python tabu search explores.
    This is the B = 1 case of `tabu_search_batched` (same compiled round
    code), so solo and batched runs follow identical trajectories.

    busy_until: optional (cloud_times, edge_times) initial machine free
    times — online replans pass the committed fleet state here, so the
    searched objective is the commit objective (DESIGN.md §7). Traced, so
    successive replans hit the same compiled search."""
    vals, assigns = tabu_search_batched(
        [jobs], None if initial is None else [list(initial)],
        max_rounds=max_rounds, objective=objective,
        machines_per_tier=(int(machines_per_tier[0]),
                           int(machines_per_tier[1])),
        busy_until=None if busy_until is None else [busy_until],
        frozen=None if frozen is None else [frozen],
        reserved=None if reserved is None else [reserved])
    return float(vals[0]), assigns[0]


def stochastic_search(jobs: Sequence[JobSpec], key,
                      initial: np.ndarray, *, iters: int = 200,
                      pop: int = 256, objective: str = "weighted",
                      machines_per_tier: Tuple[int, int] = (1, 1),
                      busy_until=None):
    """Random-restart 1-move local search, evaluated in vmapped batches.

    Each iteration proposes `pop` single-job reassignments of the incumbent
    and keeps the best. Converges to (at least) a 1-swap local optimum of
    the same neighbourhood Algorithm 2 explores, but evaluates the whole
    neighbourhood batch in one device call. Kept as the host-synced
    baseline for `tabu_search_jax` (see benchmarks/scheduler_scale.py).

    machines_per_tier / busy_until describe the fleet the schedule runs on
    (DESIGN.md §7) and are threaded into every candidate evaluation — the
    searched objective is the deployed fleet's objective, not the
    (1, 1)-idle default's.
    """
    n = len(jobs)
    rel, w, proc, trans = specs_to_arrays(jobs)
    incumbent = jnp.asarray(initial, jnp.int32)
    best = evaluate_assignments(incumbent[None], rel, w, proc, trans,
                                machines_per_tier=machines_per_tier,
                                busy_until=busy_until)
    best_v = float(best[objective][0])

    for _ in range(iters):
        key, k1, k2 = jax.random.split(key, 3)
        jobs_i = jax.random.randint(k1, (pop,), 0, n)
        machines = jax.random.randint(k2, (pop,), 0, N_MACHINES)
        cand = jnp.tile(incumbent[None], (pop, 1))
        cand = cand.at[jnp.arange(pop), jobs_i].set(machines)
        m = evaluate_assignments(cand, rel, w, proc, trans,
                                 machines_per_tier=machines_per_tier,
                                 busy_until=busy_until)
        vals = np.asarray(m[objective])
        i = int(np.argmin(vals))
        if vals[i] < best_v:
            best_v = float(vals[i])
            incumbent = cand[i]
    return best_v, np.asarray(incumbent)
