"""Expert-parallel MoE via shard_map + all-to-all (beyond-paper, §Perf 3.x).

The baseline MoE is tensor-parallel: every chip computes every expert with
d_ff split over "model", paying two (tokens x d_model) all-reduces per
layer. Expert parallelism instead PLACES each expert on a model-axis shard
group and moves the (much smaller) routed token copies with all_to_all —
the paper's workload-allocation insight applied inside the chip fleet:
compute goes where the weights live; only the job payload travels.

Layout on the "model" axis (size M) with E experts, r = M/E:
  * weights are STORED EP-major (configs.base.moe_ep_shards): shard s owns
    expert s//r's (d, f/r) slice — zero weight movement at use;
  * activations arrive sequence-sharded on "model" (the residual stream
    already is, DESIGN.md §5): each shard routes its own s_loc tokens;
  * all_to_all ships routed copies to owner shards; the expert FFN output
    is partial over f/r, completed by a psum over the r-shard expert
    group; a second all_to_all ships results back; the router-weighted
    combine is local.

Per-layer comms: 2 x all_to_all(~ s_loc*k*cf*d) + r-group psum, vs
2 x all_reduce(s_chip*d) for TP-MoE.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import policy

def ep_group_pairs(e: int, r: int):
    return [[i * r + j for j in range(r)] for i in range(e)]


def ep_moe_ffn(experts, router, h, cfg, mesh):
    """h: (B, S, d) normed MoE input (batch on dp, seq on model).
    experts: {"ep_gate","ep_up"} (E*r, d, f/r), {"ep_down"} (E*r, f/r, d).
    Returns the expert-FFN output with h's sharding + the load-balance aux.
    """
    e = cfg.num_experts
    k = cfg.num_experts_per_tok
    m = mesh.shape["model"]
    r = cfg.moe_ep_shards
    if m != e * r:
        raise ValueError(f"EP MoE needs model axis == experts x shards, "
                         f"got model={m}, experts={e}, shards={r}")
    d = cfg.d_model
    dp_axes = policy.fsdp_axes(mesh.axis_names)
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    bsz, s, _ = h.shape
    dp_total = 1
    for ax in dp_axes:
        dp_total *= mesh.shape[ax]
    # decode (seq=1) can't shard the seq dim; batch=1 can't shard dp —
    # degrade those spec entries to replicated
    seq_spec = "model" if s % m == 0 and s >= m else None
    b_spec = dp if bsz % dp_total == 0 and bsz >= dp_total else None
    s_loc = s // m if seq_spec else s
    b_loc = bsz // dp_total if b_spec else bsz
    t_loc = b_loc * s_loc                       # tokens per shard
    # capacity per EXPERT GROUP: every copy is sent to all r replicas of
    # its expert (each holds an f/r slice; the group psum completes the
    # matmul, so replicas must see identical token sets)
    send_cap = max(1, int(math.ceil(k * t_loc / e
                                    * cfg.moe_capacity_factor)))

    in_specs = (P(b_spec, seq_spec, None),     # h
                P("model", None, None),        # ep_gate
                P("model", None, None),        # ep_up
                P("model", None, None),        # ep_down
                P(None, None))                 # router
    out_specs = (P(b_spec, seq_spec, None), P())

    @partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
             out_specs=out_specs, check_vma=False)
    def run(h_loc, wg, wu, wd, rt):
        hf = h_loc.reshape(-1, d)                           # (T, d)
        t = hf.shape[0]
        logits = hf.astype(jnp.float32) @ rt                # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

        # destination EXPERT GROUP; the send block is replicated to all r
        # replica shards of the group (each computes its f/r slice)
        dest = top_e.reshape(-1)                            # (T*k,) in [0,e)
        order = jnp.argsort(dest, stable=True)
        sorted_dest = dest[order]
        counts = jnp.bincount(sorted_dest, length=e)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(t * k) - starts[sorted_dest]
        keep = rank < send_cap
        slot = jnp.where(keep, sorted_dest * send_cap + rank, e * send_cap)
        tok = order // k
        send = jnp.zeros((e * send_cap + 1, d), h_loc.dtype)
        send = send.at[slot].add(hf[tok] * keep[:, None].astype(hf.dtype))
        send = jnp.repeat(send[:-1].reshape(e, send_cap, d), r, axis=0)

        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        work = recv.reshape(m * send_cap, d)                # my expert's jobs

        act = jax.nn.silu(work @ wg[0]) * (work @ wu[0])
        out = act @ wd[0]                                   # partial (f/r)
        if r > 1:
            out = jax.lax.psum(out, "model",
                               axis_index_groups=ep_group_pairs(e, r))

        back = jax.lax.all_to_all(out.reshape(m, send_cap, d).astype(
            h_loc.dtype), "model", split_axis=0, concat_axis=0, tiled=True)
        # replicas return identical psum-complete results; keep replica 0
        back = back.reshape(e, r, send_cap, d)[:, 0].reshape(
            e * send_cap, d)

        w_sorted = top_w.reshape(-1)[order]
        contrib = back[jnp.where(keep, slot, 0)] \
            * (w_sorted * keep).astype(back.dtype)[:, None]
        y = jnp.zeros((t, d), back.dtype).at[tok].add(contrib)

        frac = jnp.mean(jax.nn.one_hot(top_e[..., 0], e,
                                       dtype=jnp.float32), axis=0)
        mean_p = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac * mean_p)
        aux = jax.lax.pmean(aux, "model")
        for ax in (dp_axes if isinstance(dp, tuple) else (dp,)):
            aux = jax.lax.pmean(aux, ax)
        return y.reshape(h_loc.shape), aux

    h = policy.constrain(h, (policy.DP, policy.TP, None))
    return run(h, experts["ep_gate"], experts["ep_up"], experts["ep_down"],
               router)
