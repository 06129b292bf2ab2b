"""Expert-parallel MoE dispatch/combine over the 'ep' mesh axis.

Ref: python/paddle/incubate/distributed/models/moe/moe_layer.py +
global_scatter/global_gather collective ops. The reference dispatches tokens
with capacity-bucketed all-to-all (brpc/NCCL global_scatter). TPU-native:
the r5 SLOT SCHEDULE (row gathers into MXU-tiled capacity buckets with
gather-only vjps) at ep=1 and, inside a manual shard_map over (dp, ep),
at ep>1 (moe_slot_dispatch_local — local-expert gathers + one [T,D] psum);
the capacity-bucketed one-hot einsum form (GSPMD all-to-all) and the
explicit all-to-all moe_shard_map_dispatch remain as alternates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size as _axis_size

from .. import envs
from ..observability import trace as _obs


def default_dispatch_mode():
    """Dispatch mode from the environment: PADDLE_TPU_MOE_DROPLESS=1 turns
    on the ragged grouped-GEMM path; unset/0 keeps the capacity slot
    schedule (reference drop parity)."""
    return envs.get("PADDLE_TPU_MOE_DROPLESS")


def _gshard_aux_loss(probs, E):
    """gshard load-balancing loss: E * sum(mean_prob * fraction_top1).
    ONE definition shared by the one-hot and slot-schedule gates — their
    numerical parity is test-asserted."""
    top1 = jnp.argmax(probs, axis=-1)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top1, E, dtype=probs.dtype), axis=0)
    return E * jnp.sum(me * ce)


def top_k_gating(logits, k: int, capacity: int, drop_capacity=None):
    """gshard/switch gating. logits [T, E] fp32. Returns (combine [T, E, C],
    dispatch [T, E, C] bool, aux_loss scalar).

    ``drop_capacity`` (default: ``capacity``) is the per-expert queue
    length beyond which tokens drop; the [T, E, C] buffers stay sized by
    ``capacity``. Passing the unrounded reference capacity here gives
    reference-exact drop accounting while compute stays MXU-tiled."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    gates = jnp.zeros_like(probs)
    remaining = probs
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        onehot = jax.nn.one_hot(idx, E, dtype=probs.dtype)
        gates = gates + onehot * probs
        remaining = remaining * (1 - onehot)

    aux_loss = _gshard_aux_loss(probs, E)

    # capacity assignment: position of each token within its expert queue
    if drop_capacity is None:
        drop_capacity = capacity
    chosen = gates > 0  # [T, E]
    position_in_expert = (jnp.cumsum(chosen, axis=0) - 1) * chosen  # [T, E]
    in_capacity = chosen & (position_in_expert < min(drop_capacity, capacity))
    pos_oh = jax.nn.one_hot(position_in_expert, capacity, dtype=probs.dtype)  # [T,E,C]
    dispatch = pos_oh * in_capacity[..., None]
    combine = dispatch * gates[..., None]
    # renormalize combine weights over selected experts
    denom = combine.sum(axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9) * gates.sum(-1)[:, None, None]
    return combine, dispatch, aux_loss


def _round_up(n, m):
    return -(-n // m) * m


def _ref_capacity(T, k, E, capacity_factor):
    """The reference's per-expert capacity (moe_layer.py: floor of
    cap_factor * tokens * k / experts, min 1) — UNROUNDED."""
    return max(int(capacity_factor * T * k / E), 1)


def _capacity(T, k, E, capacity_factor):
    """ONE capacity formula for every dispatch path (ep=1 slot schedule,
    ep>1 local slot schedule, one-hot einsum): MXU-tiled 128-rounded
    per-expert bucket size for T routed tokens."""
    return _round_up(_ref_capacity(T, k, E, capacity_factor), 128)


def moe_capacity(T, k, E, capacity_factor):
    """(compute_capacity, reference_capacity) for drop accounting.

    The slot schedule sizes its buckets by the 128-rounded compute
    capacity so expert matmul rows stay MXU-tiled; the reference drops
    tokens at the UNROUNDED capacity. Rounding up therefore admits up to
    127 extra tokens per expert that the reference would drop (strictly
    fewer drops — a quality upside, but a parity deviation; PARITY.md).
    Dispatch entry points take ``strict_capacity=True`` to drop at the
    reference capacity while keeping the rounded buffers."""
    return _capacity(T, k, E, capacity_factor), \
        _ref_capacity(T, k, E, capacity_factor)


def topk_route(logits, k: int, capacity: int, drop_capacity=None):
    """Slot-schedule routing (no [T,E,C] one-hots). logits [T, E] fp32.

    Returns (slot [T*k] int32 in [0, E*C] with E*C = the trash slot for
    capacity-dropped pairs, weight [T, k] f32 combine weights, aux_loss).
    Pair order is token-major, so per-expert queue positions match the
    gshard cumsum-over-tokens assignment the one-hot path used.

    ``drop_capacity`` (default: ``capacity``) caps each expert's queue
    for DROP purposes only; slots beyond it route to the trash slot
    while the bucket layout stays ``capacity`` rows per expert. Pass the
    unrounded reference capacity for reference-exact drop accounting."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, experts = lax.top_k(probs, k)            # [T, k] each
    aux_loss = _gshard_aux_loss(probs, E)

    e_flat = experts.reshape(-1)                    # [T*k] token-major
    oh = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)  # [T*k, E] (tiny)
    pos = (jnp.cumsum(oh, axis=0) - oh)             # exclusive prefix count
    pos = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]
    if drop_capacity is None:
        drop_capacity = capacity
    valid = pos < min(drop_capacity, capacity)
    slot = jnp.where(valid, e_flat * capacity + pos, E * capacity)

    # combine weights: renormalize so each token's surviving gates carry
    # the full selected mass (the one-hot path's denom dance)
    g = gates * valid.reshape(T, k)
    denom = jnp.maximum(g.sum(-1, keepdims=True), 1e-9)
    weight = g / denom * gates.sum(-1, keepdims=True)
    return slot.astype(jnp.int32), weight, aux_loss


def ragged_buffer_rows(T, k, E, tile_rows):
    """Static row count of the dropless expert-sorted token buffer.

    Each expert's group is padded up to a tile boundary (at most
    tile_rows-1 dead rows per expert), so round_up(T*k) + E*tile_rows
    always covers the dynamic sum of aligned group sizes. Rows past the
    last group are dead tail tiles the kernel zero-fills."""
    return _round_up(T * k, tile_rows) + E * tile_rows


def ragged_route(logits, k: int, tile_rows: int):
    """DROPLESS routing into a tile-aligned expert-sorted buffer.

    logits [T, E] fp32. Returns (slot [T*k] int32, weight [T, k] f32,
    aux_loss, counts [E] int32, n_rows static int). Every (token, choice)
    pair gets a row: slot = group_offset[expert] + queue position, where
    group offsets come from the cumsum of tile-ROUNDED per-expert counts
    (so each expert's rows start MXU-tile-aligned and the grouped-matmul
    grid needs no intra-tile group switches). No capacity, no trash slot
    for routed pairs — the only dead rows are the per-expert alignment
    pads and the static tail, and those read the sentinel zero row.

    Queue positions are the same token-major cumsum ``topk_route`` uses,
    and the combine-weight formula is copied verbatim (with every pair
    valid), so a no-drop capacity run and a ragged run see bit-identical
    weights."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, experts = lax.top_k(probs, k)            # [T, k] each
    aux_loss = _gshard_aux_loss(probs, E)

    e_flat = experts.reshape(-1)                    # [T*k] token-major
    oh = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)  # [T*k, E] (tiny)
    pos = (jnp.cumsum(oh, axis=0) - oh)             # exclusive prefix count
    pos = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]
    counts = oh.sum(axis=0).astype(jnp.int32)       # [E] group sizes
    offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(_round_up(counts, tile_rows)).astype(jnp.int32)])
    slot = offsets[e_flat] + pos

    # same renormalization dance as topk_route with valid == all-true so
    # the no-drop capacity weights match bitwise
    g = gates
    denom = jnp.maximum(g.sum(-1, keepdims=True), 1e-9)
    weight = g / denom * gates.sum(-1, keepdims=True)
    n_rows = ragged_buffer_rows(T, k, E, tile_rows)
    return slot.astype(jnp.int32), weight, aux_loss, counts, n_rows


# ---------------------------------------------------------------------------
# Routing statistics (on-device, returned as auxiliary outputs — telemetry
# reads them AFTER the step, never syncing inside it). All values are f32
# scalars so they ride along any jitted output pytree.
# ---------------------------------------------------------------------------

def routing_stats(slot, num_experts, capacity, k, drop_capacity=None):
    """Per-step routing stats from a slot-schedule assignment.

    slot: [T*k] int32 from ``topk_route`` (E*capacity = trash slot).
    Returns {moe_dropped_tokens, moe_routed_tokens, moe_load_imbalance
    (max/mean expert load), moe_capacity_util (routed / total drop-capacity
    rows)} — all f32 scalars.
    """
    E = num_experts
    if drop_capacity is None:
        drop_capacity = capacity
    valid = (slot < E * capacity).astype(jnp.float32)        # [T*k]
    routed = valid.sum()
    dropped = jnp.asarray(slot.shape[0], jnp.float32) - routed
    expert_of = jnp.clip(slot // capacity, 0, E - 1)
    load = jnp.zeros((E,), jnp.float32).at[expert_of].add(valid)
    mean = jnp.maximum(routed / E, 1e-9)
    imbalance = load.max() / mean
    util = routed / float(E * min(drop_capacity, capacity))
    return {"moe_dropped_tokens": dropped,
            "moe_routed_tokens": routed,
            "moe_load_imbalance": imbalance,
            "moe_capacity_util": util}


def routing_stats_onehot(dispatch, k, drop_capacity=None):
    """Routing stats from a one-hot [T, E, C] dispatch mask (``top_k_gating``
    path). Same keys/semantics as ``routing_stats``."""
    T, E, C = dispatch.shape
    if drop_capacity is None:
        drop_capacity = C
    load = dispatch.astype(jnp.float32).sum(axis=(0, 2))     # [E]
    routed = load.sum()
    dropped = jnp.asarray(T * k, jnp.float32) - routed
    mean = jnp.maximum(routed / E, 1e-9)
    imbalance = load.max() / mean
    util = routed / float(E * min(drop_capacity, C))
    return {"moe_dropped_tokens": dropped,
            "moe_routed_tokens": routed,
            "moe_load_imbalance": imbalance,
            "moe_capacity_util": util}


def routing_stats_ragged(counts, k, tile_rows):
    """Per-step routing stats for the DROPLESS ragged path.

    counts: [E] int32 per-expert group sizes from ``ragged_route``.
    Dropless means drops are structurally zero — moe_dropped_tokens is an
    explicit 0 (not a fabricated capacity number), and the vacuous
    capacity-utilization stat is replaced by the quantities that matter
    for a ragged schedule: live vs tile-alignment-padded rows and the
    per-expert group sizes themselves."""
    counts_f = counts.astype(jnp.float32)
    E = counts.shape[0]
    live = counts_f.sum()
    padded = _round_up(counts, tile_rows).astype(jnp.float32).sum() - live
    mean = jnp.maximum(live / E, 1e-9)
    return {"moe_dropped_tokens": jnp.zeros((), jnp.float32),
            "moe_routed_tokens": live,
            "moe_load_imbalance": counts_f.max() / mean,
            "moe_live_rows": live,
            "moe_padded_rows": padded,
            "moe_expert_rows": counts_f}


#: stats keys that are RATIOS — aggregate by averaging (over dp shards
#: and over MoE layers); every other key is a count and sums.
RATIO_STAT_KEYS = ("moe_load_imbalance", "moe_capacity_util")


def zero_routing_stats(mode: str = "capacity", num_experts: int = 0):
    """The stats pytree with all-zero values (layers without MoE / masking).

    ``mode`` selects the key set ("capacity" default — the historical
    4-scalar dict — or "ragged"); ragged needs ``num_experts`` for the
    [E] per-expert group-size vector so dense/MoE lax.cond branches agree
    on structure."""
    z = jnp.zeros((), jnp.float32)
    if mode == "ragged":
        return {"moe_dropped_tokens": z, "moe_routed_tokens": z,
                "moe_load_imbalance": z, "moe_live_rows": z,
                "moe_padded_rows": z,
                "moe_expert_rows": jnp.zeros((num_experts,), jnp.float32)}
    if mode == "ragged_a2a":
        return {"moe_dropped_tokens": z, "moe_routed_tokens": z,
                "moe_load_imbalance": z, "moe_live_rows": z,
                "moe_padded_rows": z, "moe_a2a_wire_rows": z,
                "moe_a2a_buffer_rows": z,
                "moe_expert_rows": jnp.zeros((num_experts,), jnp.float32)}
    return {"moe_dropped_tokens": z, "moe_routed_tokens": z,
            "moe_load_imbalance": z, "moe_capacity_util": z}


def moe_dispatch_combine(x, gate_logits, expert_fn, expert_params, num_experts,
                         k=2, capacity_factor=1.25, use_onehot=False,
                         strict_capacity=False, return_stats=False,
                         dispatch_mode=None, act=jax.nn.gelu):
    """MoE dispatch/combine. x [T, D] tokens, expert_params stacked [E, ...].

    Default path (single-device / ep=1): SLOT SCHEDULE — each routed
    (token, choice) pair gets a slot in its expert's capacity bucket; the
    expert inputs are one row-GATHER of x in slot order ([E*C, D]), the
    combine is one row-gather of the expert outputs weighted by the gate.
    Replaces the one-hot einsum dispatch whose [T,E,C] x [T,D] matmuls
    cost ~E*C/(k) times the useful expert FLOPs (the r4 profile: 0.195
    active MFU with dispatch/combine dominant). Capacity is rounded up
    to a multiple of 128 so the expert matmul rows stay MXU-tiled.

    use_onehot=True keeps the einsum form whose vocab-style contraction
    GSPMD partitions into the ep all-to-all cleanly (gathers over a
    sharded token dim would involuntarily rematerialize). It serves
    mesh-less ep>1 callers only — models with a mesh route ep>1 through
    the moe_slot_dispatch_local shard_map island instead.

    strict_capacity=True drops tokens at the UNROUNDED reference
    capacity (see moe_capacity) instead of the 128-rounded bucket size —
    reference-exact drop accounting at the cost of up to 127 usable
    bucket rows per expert going idle.

    return_stats=True appends a ``routing_stats`` dict as a third output
    (on-device f32 scalars: drops, load imbalance, capacity utilization)
    for step telemetry; default keeps the 2-tuple API.

    dispatch_mode selects "capacity" (default; also the
    PADDLE_TPU_MOE_DROPLESS=0 env default) or "ragged" — the DROPLESS
    grouped-GEMM path (moe_ragged_dispatch_combine). Ragged requires
    ``expert_params`` to be the 2-tuple of stacked FFN weights
    ``(w1 [E,H,I], w2 [E,I,H])`` with ``act`` between them (expert_fn is
    ignored: the grouped kernel needs the matmul structure, not an opaque
    callable)."""
    if dispatch_mode is None:
        dispatch_mode = default_dispatch_mode()
    if dispatch_mode == "ragged":
        w1, w2 = expert_params
        return moe_ragged_dispatch_combine(
            x, gate_logits, w1, w2, num_experts, k=k, act=act,
            return_stats=return_stats)
    if dispatch_mode != "capacity":
        raise ValueError(f"unknown dispatch_mode {dispatch_mode!r} "
                         "(expected 'capacity' or 'ragged')")
    T, D = x.shape
    capacity, ref_cap = moe_capacity(T, k, num_experts, capacity_factor)
    drop_cap = ref_cap if strict_capacity else capacity
    if use_onehot:
        combine, dispatch, aux = top_k_gating(gate_logits, k, capacity,
                                              drop_capacity=drop_cap)
        # [T,E,C] x [T,D] -> [E,C,D]
        expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
        expert_out = jax.vmap(expert_fn)(expert_params, expert_in)
        out = jnp.einsum("tec,ecd->td", combine.astype(expert_out.dtype),
                         expert_out)
        if return_stats:
            return out, aux, routing_stats_onehot(dispatch, k,
                                                  drop_capacity=drop_cap)
        return out, aux

    E = num_experts
    slot, weight, aux = topk_route(gate_logits, k, capacity,
                                   drop_capacity=drop_cap)

    # slot -> source token (E*C is the trash slot; sentinel token T reads
    # the appended zero row, so dropped/unfilled slots compute on zeros)
    token_of_pair = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    inv = jnp.full((E * capacity + 1,), T, jnp.int32).at[slot].set(
        token_of_pair, mode="drop")
    # slot -> source PAIR (for the combine gather's transpose)
    pair_inv = jnp.full((E * capacity + 1,), T * k, jnp.int32).at[slot].set(
        jnp.arange(T * k, dtype=jnp.int32), mode="drop")

    expert_in = _dispatch_rows(x, inv, slot, k).reshape(E, capacity, D)
    expert_out = jax.vmap(expert_fn)(expert_params, expert_in)  # [E,C,D']
    d_out = expert_out.shape[-1]
    picked = _combine_rows(expert_out.reshape(E * capacity, d_out),
                           slot, pair_inv).reshape(T, k, d_out)
    out = jnp.einsum("tk,tkd->td", weight.astype(picked.dtype), picked)
    if return_stats:
        return out, aux, routing_stats(slot, E, capacity, k,
                                       drop_capacity=drop_cap)
    return out, aux


def moe_ragged_dispatch_combine(x, gate_logits, w1, w2, num_experts, k=2,
                                act=jax.nn.gelu, tile_rows=None,
                                return_stats=False):
    """DROPLESS MoE: ragged grouped-GEMM expert compute (MegaBlocks-style).

    x [T, D] tokens; w1 [E, D, I] / w2 [E, I, D] stacked expert FFN
    weights. Routing (``ragged_route``) lays every (token, choice) pair
    into a tile-aligned expert-sorted buffer — no capacity buckets, no
    drops; padding is bounded by one MXU row tile per expert plus a
    static tail. The expert FFN then runs as two Pallas grouped matmuls
    over ONE fixed grid of row tiles whose per-tile expert/live flags
    come from the group boundaries (SMEM scalar prefetch) — each
    expert's rows are computed exactly once, on real data.

    Dispatch/combine reuse the slot schedule's gather-only custom vjps
    (`_dispatch_rows`/`_combine_rows`) with the sentinel row mapping the
    alignment pads and static tail to zeros.

    return_stats=True appends ``routing_stats_ragged`` (explicit
    drops=0, live-vs-padded rows, per-expert group sizes)."""
    from ..ops.grouped_matmul import TILE_ROWS, grouped_matmul, tile_schedule
    if tile_rows is None:
        tile_rows = TILE_ROWS
    T, D = x.shape
    E = num_experts
    slot, weight, aux, counts, n_rows = ragged_route(gate_logits, k,
                                                     tile_rows)
    sched = tile_schedule(counts, n_rows // tile_rows, tile_rows)[:4]

    token_of_pair = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    inv = jnp.full((n_rows + 1,), T, jnp.int32).at[slot].set(
        token_of_pair, mode="drop")
    pair_inv = jnp.full((n_rows + 1,), T * k, jnp.int32).at[slot].set(
        jnp.arange(T * k, dtype=jnp.int32), mode="drop")

    xd = _dispatch_rows(x, inv, slot, k)            # [n_rows, D]
    h = act(grouped_matmul(xd, w1, sched, tile_rows))
    y = grouped_matmul(h, w2, sched, tile_rows)     # [n_rows, D']
    d_out = y.shape[-1]
    picked = _combine_rows(y, slot, pair_inv).reshape(T, k, d_out)
    out = jnp.einsum("tk,tkd->td", weight.astype(picked.dtype), picked)
    if return_stats:
        return out, aux, routing_stats_ragged(counts, k, tile_rows)
    return out, aux


# Both routing gathers carry GATHER-ONLY custom vjps: slots are unique
# per routed pair, so each transpose (naturally a scatter-add) is exactly
# another row gather through the precomputed inverse index — XLA's
# scatter lowering cost ~0.8 ms/layer in the r5 profile; these are free.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(x, inv, slot, k):
    """[E*C, D] expert-slot rows from token rows (sentinel -> zeros)."""
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)], 0)
    return x_pad[inv[:-1]]


def _dispatch_rows_fwd(x, inv, slot, k):
    return _dispatch_rows(x, inv, slot, k), (x.shape[0], inv, slot)


def _dispatch_rows_bwd(k, res, g):
    T, inv, slot = res
    g_pad = jnp.concatenate([g, jnp.zeros((1, g.shape[1]), g.dtype)], 0)
    # d_x[t] = sum over the token's k routed slots (trash slot -> zero row)
    d_x = g_pad[slot].reshape(T, k, g.shape[1]).sum(axis=1)
    return d_x, None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(flat, slot, pair_inv):
    """[T*k, D] per-pair rows from expert-slot rows (trash -> zeros)."""
    f_pad = jnp.concatenate([flat, jnp.zeros((1, flat.shape[1]),
                                             flat.dtype)], 0)
    return f_pad[slot]


def _combine_rows_fwd(flat, slot, pair_inv):
    return _combine_rows(flat, slot, pair_inv), pair_inv


def _combine_rows_bwd(pair_inv, g):
    g_pad = jnp.concatenate([g, jnp.zeros((1, g.shape[1]), g.dtype)], 0)
    return g_pad[pair_inv[:-1]], None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


# lax.optimization_barrier has no AD rule on 0.4.x; the blocking a2a
# schedule needs a differentiable one. Identity either way — the barrier
# only pins scheduling — and the cotangents are barriered too so the
# backward pass keeps the same blocking shape.
@jax.custom_vjp
def _blocking_barrier(xs):
    return lax.optimization_barrier(xs)


def _blocking_barrier_fwd(xs):
    return _blocking_barrier(xs), None


def _blocking_barrier_bwd(_, g):
    return (lax.optimization_barrier(g),)


_blocking_barrier.defvjp(_blocking_barrier_fwd, _blocking_barrier_bwd)


def moe_slot_dispatch_local(x, gate_logits, expert_fn, expert_params_local,
                            num_experts, axis_name="ep", k=2,
                            capacity_factor=1.25, strict_capacity=False,
                            return_stats=False):
    """Slot-schedule MoE INSIDE a manual shard_map over `axis_name` (r5):
    each ep shard holds E/n experts and its local tokens; it computes the
    full top-k routing, gathers ONLY the slots belonging to its local
    experts, runs them, and the combine psums partial outputs over 'ep'
    (each token's k expert outputs live on exactly the owning shards).
    Replaces the one-hot einsum dispatch at ep>1 with the same row-gather
    schedule the ep=1 path uses — no [T,E,C] one-hots, no all-to-all of
    padded capacity buckets (the psum moves [T,D] once).

    x [T_local, D] this shard's tokens; expert_params_local leaves with
    leading dim E/n. Same capacity formula and queue positions as
    moe_dispatch_combine, but capacity is sized from the dp-LOCAL token
    count: identical to serial when nothing is dropped (test-asserted);
    under capacity overflow at dp>1 the drop sets may differ from the
    global-batch formula."""
    n = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    T, D = x.shape
    E = num_experts
    e_local = E // n
    # capacity from the LOCAL (per-dp-shard) token count — the
    # reference's MoE also sizes capacity from the local batch. With no
    # drops this matches the serial/einsum path exactly (test-asserted);
    # when a skewed router overflows capacity at dp>1, drop sets can
    # differ from the global-batch formula.
    capacity, ref_cap = moe_capacity(T, k, E, capacity_factor)
    slot, weight, aux = topk_route(
        gate_logits, k, capacity,
        drop_capacity=ref_cap if strict_capacity else capacity)

    # keep only slots owned by THIS shard's experts; re-base to local
    lo = idx * e_local * capacity
    local_span = e_local * capacity
    loc = slot - lo
    mine = (loc >= 0) & (loc < local_span)
    loc = jnp.where(mine, loc, local_span)          # local trash slot
    token_of_pair = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    inv = jnp.full((local_span + 1,), T, jnp.int32).at[loc].set(
        token_of_pair, mode="drop")
    pair_inv = jnp.full((local_span + 1,), T * k, jnp.int32).at[loc].set(
        jnp.arange(T * k, dtype=jnp.int32), mode="drop")

    expert_in = _dispatch_rows(x, inv, loc, k).reshape(
        e_local, capacity, D)
    expert_out = jax.vmap(expert_fn)(expert_params_local, expert_in)
    d_out = expert_out.shape[-1]
    picked = _combine_rows(expert_out.reshape(local_span, d_out),
                           loc, pair_inv).reshape(T, k, d_out)
    w = weight * mine.reshape(T, k)                 # remote pairs -> 0
    partial = jnp.einsum("tk,tkd->td", w.astype(picked.dtype), picked)
    with _obs.comm_span("moe.combine_psum",
                        nbytes=partial.size * partial.dtype.itemsize,
                        site="moe.combine_psum"):
        out = lax.psum(partial, axis_name)
    if return_stats:
        # routing is computed identically on every ep shard from this dp
        # shard's (ep-replicated) tokens, so the stats are per-dp-shard
        # values replicated over ep; the caller aggregates over dp.
        return out, aux, routing_stats(
            slot, E, capacity, k,
            drop_capacity=ref_cap if strict_capacity else capacity)
    return out, aux


def moe_ragged_dispatch_local(x, gate_logits, w1_local, w2_local,
                              num_experts, axis_name="ep", k=2,
                              act=jax.nn.gelu, tile_rows=None,
                              return_stats=False):
    """DROPLESS ragged MoE INSIDE a manual shard_map over `axis_name`:
    the ragged analogue of moe_slot_dispatch_local. Each ep shard
    computes the full top-k routing over its (dp-local, ep-replicated)
    tokens, keeps only the pairs routed to its LOCAL experts, lays them
    into a local tile-aligned ragged buffer (group boundaries over
    E/n local experts), runs the two grouped matmuls, and the combine
    psums [T, D] partials over 'ep' exactly as the slot schedule does —
    the collective is unchanged, only the expert compute is ragged.

    Because routing is dropless, shard outputs are equivalent to the
    serial ragged path regardless of load skew (no per-shard capacity
    semantics to diverge; test-asserted at ep=2).

    return_stats: group sizes/imbalance are computed from the GLOBAL
    per-expert counts (identical on every ep shard); padded rows differ
    per shard (each pads its own local groups) and are psum'd over 'ep'
    so the returned stats are ep-replicated like the slot path's."""
    from ..ops.grouped_matmul import TILE_ROWS, grouped_matmul, tile_schedule
    if tile_rows is None:
        tile_rows = TILE_ROWS
    n = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    T, D = x.shape
    E = num_experts
    e_local = E // n

    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    gates, experts = lax.top_k(probs, k)
    aux = _gshard_aux_loss(probs, E)
    e_flat = experts.reshape(-1)                    # [T*k] token-major

    # local-expert group layout: pairs owned by this shard bucket by
    # LOCAL expert id; remote pairs go to a trash bucket whose queue we
    # never materialize (slot -> the sentinel row n_rows)
    le = e_flat - idx * e_local
    mine = (le >= 0) & (le < e_local)
    le_t = jnp.where(mine, le, e_local)             # e_local = trash bucket
    oh = jax.nn.one_hot(le_t, e_local + 1, dtype=jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) - oh)
    pos = jnp.take_along_axis(pos, le_t[:, None], axis=1)[:, 0]
    counts = oh.sum(axis=0)[:e_local].astype(jnp.int32)
    offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(_round_up(counts, tile_rows)).astype(jnp.int32)])
    # worst case every pair is local -> same static bound as serial with
    # E/n groups
    n_rows = ragged_buffer_rows(T, k, e_local, tile_rows)
    slot = jnp.where(mine, offsets[le_t] + pos, n_rows).astype(jnp.int32)
    sched = tile_schedule(counts, n_rows // tile_rows, tile_rows)[:4]

    token_of_pair = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    inv = jnp.full((n_rows + 1,), T, jnp.int32).at[slot].set(
        token_of_pair, mode="drop")
    pair_inv = jnp.full((n_rows + 1,), T * k, jnp.int32).at[slot].set(
        jnp.arange(T * k, dtype=jnp.int32), mode="drop")

    xd = _dispatch_rows(x, inv, slot, k)
    h = act(grouped_matmul(xd, w1_local, sched, tile_rows))
    y = grouped_matmul(h, w2_local, sched, tile_rows)
    d_out = y.shape[-1]
    picked = _combine_rows(y, slot, pair_inv).reshape(T, k, d_out)

    # same combine-weight formula as ragged_route (all pairs valid);
    # remote pairs zeroed so the psum sums each pair exactly once
    denom = jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    weight = gates / denom * gates.sum(-1, keepdims=True)
    w = weight * mine.reshape(T, k)
    partial = jnp.einsum("tk,tkd->td", w.astype(picked.dtype), picked)
    with _obs.comm_span("moe.combine_psum",
                        nbytes=partial.size * partial.dtype.itemsize,
                        site="moe.combine_psum"):
        out = lax.psum(partial, axis_name)
    if return_stats:
        g_counts = jax.nn.one_hot(e_flat, E, dtype=jnp.int32).sum(axis=0)
        st = routing_stats_ragged(g_counts.astype(jnp.int32), k, tile_rows)
        local_pad = (_round_up(counts, tile_rows).astype(jnp.float32).sum()
                     - counts.astype(jnp.float32).sum())
        st["moe_padded_rows"] = lax.psum(local_pad, axis_name)
        return out, aux, st
    return out, aux


def moe_ragged_dispatch_a2a(x, gate_logits, w1_local, w2_local, num_experts,
                            axis_name="ep", k=2, act=jax.nn.gelu,
                            tile_rows=None, a2a_impl=None, overlap=None,
                            return_stats=False):
    """Skew-proof expert parallelism: RAGGED all-to-all dispatch (PR 10).

    Unlike ``moe_ragged_dispatch_local`` (ep-replicated tokens, [T, D]
    combine psum), tokens here are SHARDED over ``axis_name``: x
    [T_local, D] is this rank's slice, each rank owns E/n experts, and
    every routed (token, choice) pair travels to its expert's owner and
    its FFN output travels back — the reference's global_scatter /
    global_gather, but with UNEVEN splits so wire bytes track the real
    router distribution instead of a cf-padded capacity bucket.

    Layout: pairs sort into per-DESTINATION chunks laid out HOP-major —
    chunk h holds the rows for rank (me + h) % n, with the destination's
    local-expert groups tile-aligned inside the chunk (the cumsum-of-
    rounded-counts layout ``chunk_schedule`` re-derives on the receiver
    from the exchanged counts, so sender packing and receiver schedule
    agree with no index traffic). Every chunk is ``chunk_rows`` =
    ``ragged_buffer_rows(T, k, E/n, tile_rows)`` rows — the worst case of
    ALL local pairs addressing one rank — so adversarial skew can never
    overflow a chunk: ragged mode has NO drops under ANY routing
    (test-pinned; capacity-mode overflow semantics live in
    ``moe_shard_map_dispatch``). Dead rows gather the sentinel zero row
    and dead tiles are predicated off in the grouped kernel, so only the
    schedule (not the values) sees the padding.

    Transport (``a2a_impl``, default env ``PADDLE_TPU_MOE_A2A``):
    'ring' walks n-1 ``ppermute`` hops (hop h = shift by h); 'dense'
    ships the identical hop-major chunks through one XLA all_to_all.
    ``overlap`` (default env ``PADDLE_TPU_MOE_A2A_OVERLAP``) drops the
    blocking optimization_barrier in ring mode so the grouped-GEMM on
    hop h's chunk is free to run while hop h+1's ppermute is in flight
    — each chunk has its own ``chunk_schedule``, so no compute waits on
    the last hop. All four {ring, dense} x {overlap, blocking} variants
    run the identical per-chunk kernels on identical rows and are
    BITWISE-equal (full-K dots, no cross-chunk reduction).

    The combine is a row gather of the returned chunks weighted by this
    rank's own gates — no psum; the output stays sharded like x.

    return_stats=True appends the ragged stats dict (ep-global expert
    counts — ``moe_expert_rows`` feeds active-only optimizer masking —
    plus wire accounting: ``moe_a2a_wire_rows`` = real rows that crossed
    the wire, ``moe_a2a_buffer_rows`` = chunk rows shipped incl. padding),
    psum'd over ``axis_name`` so every ep rank reports the group total."""
    from ..ops.grouped_matmul import (TILE_ROWS, chunk_schedule,
                                      grouped_matmul)
    if tile_rows is None:
        tile_rows = TILE_ROWS
    if a2a_impl is None:
        a2a_impl = envs.get("PADDLE_TPU_MOE_A2A")
    if a2a_impl not in ("ring", "dense"):
        raise ValueError(f"unknown a2a_impl {a2a_impl!r} "
                         "(expected 'ring' or 'dense')")
    if overlap is None:
        overlap = envs.get("PADDLE_TPU_MOE_A2A_OVERLAP")
    from ..distributed.communication.ragged import exchange_counts, ring_hop
    n = _axis_size(axis_name)
    me = lax.axis_index(axis_name)
    T, D = x.shape
    E = num_experts
    e_local = E // n

    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    gates, experts = lax.top_k(probs, k)
    aux = _gshard_aux_loss(probs, E)
    e_flat = experts.reshape(-1)                    # [T*k] token-major

    # queue position within the (destination, local-expert) group — the
    # global expert id keys both, so the plain per-expert cumsum serves
    oh = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) - oh)
    pos = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]
    counts = oh.sum(axis=0).astype(jnp.int32)       # [E] rows per expert
    counts_mat = counts.reshape(n, e_local)         # [dest, local expert]
    aligned = _round_up(counts_mat, tile_rows)
    off_within = jnp.concatenate([
        jnp.zeros((n, 1), jnp.int32),
        jnp.cumsum(aligned, axis=1).astype(jnp.int32)[:, :-1]], axis=1)

    # hop-major chunks: chunk h goes to rank (me + h) % n. chunk_rows is
    # the all-pairs-to-one-rank worst case -> skew cannot overflow.
    chunk_rows = ragged_buffer_rows(T, k, e_local, tile_rows)
    dest = e_flat // e_local
    le = e_flat % e_local
    hop = (dest - me) % n
    slot = (hop * chunk_rows + off_within[dest, le] + pos).astype(jnp.int32)
    n_rows = n * chunk_rows

    token_of_pair = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    inv = jnp.full((n_rows + 1,), T, jnp.int32).at[slot].set(
        token_of_pair, mode="drop")
    pair_inv = jnp.full((n_rows + 1,), T * k, jnp.int32).at[slot].set(
        jnp.arange(T * k, dtype=jnp.int32), mode="drop")

    send = _dispatch_rows(x, inv, slot, k).reshape(n, chunk_rows, D)
    # rows per my-local-expert each SOURCE rank is sending me
    recv_counts = exchange_counts(counts_mat, axis_name,
                                  name="moe.ragged_a2a.counts")

    ring = a2a_impl == "ring" and n > 1
    if ring:
        chunks = [send[0]]
        for h in range(1, n):
            chunks.append(ring_hop(send[h], axis_name, h,
                                   name="moe.ragged_a2a.hop"))
    elif n > 1:
        # dense fallback: same chunks, one collective. hop-major -> dest-
        # major on the way out, source-major -> hop-major on the way in.
        dest_major = jnp.roll(send, me, axis=0)
        with _obs.comm_span("moe.ragged_a2a.dense",
                            nbytes=send.size * send.dtype.itemsize,
                            site="moe.ragged_a2a"):
            recv_src = lax.all_to_all(dest_major, axis_name, split_axis=0,
                                      concat_axis=0, tiled=True)
        hop_major = jnp.roll(recv_src[::-1], me + 1, axis=0)
        chunks = [hop_major[h] for h in range(n)]
    else:
        chunks = [send[0]]
    overlapping = bool(overlap) and ring
    if n > 1:
        _obs.record_counter("moe.a2a.hops_total", n - 1)
        if overlapping:
            _obs.record_counter("moe.a2a.hops_overlapped", n - 1)
        else:
            # blocking schedule: no chunk's GEMM starts until every hop
            # has landed (the barrier ties all chunks together)
            chunks = list(_blocking_barrier(tuple(chunks)))

    ys = []
    for h in range(n):
        src = (me - h) % n
        cnts = jnp.take(recv_counts, src, axis=0)   # [e_local]
        sched = chunk_schedule(cnts, chunk_rows, tile_rows)
        hid = act(grouped_matmul(chunks[h], w1_local, sched, tile_rows))
        ys.append(grouped_matmul(hid, w2_local, sched, tile_rows))

    if ring:
        ret = [ys[0]]
        for h in range(1, n):
            ret.append(ring_hop(ys[h], axis_name, -h,
                                name="moe.ragged_a2a.ret_hop"))
    elif n > 1:
        stack_y = jnp.stack(ys)                     # [hop, chunk_rows, D']
        tosrc = jnp.roll(stack_y[::-1], me + 1, axis=0)  # [source, ...]
        with _obs.comm_span("moe.ragged_a2a.dense_ret",
                            nbytes=stack_y.size * stack_y.dtype.itemsize,
                            site="moe.ragged_a2a"):
            ret_src = lax.all_to_all(tosrc, axis_name, split_axis=0,
                                     concat_axis=0, tiled=True)
        ret_hop = jnp.roll(ret_src, -me, axis=0)
        ret = [ret_hop[h] for h in range(n)]
    else:
        ret = [ys[0]]

    y_all = jnp.concatenate(ret, axis=0)            # [n_rows, D']
    d_out = y_all.shape[-1]
    picked = _combine_rows(y_all, slot, pair_inv).reshape(T, k, d_out)
    # same combine-weight formula as ragged_route (every pair valid)
    denom = jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    weight = gates / denom * gates.sum(-1, keepdims=True)
    out = jnp.einsum("tk,tkd->td", weight.astype(picked.dtype), picked)
    if return_stats:
        g_counts = lax.psum(counts, axis_name)      # ep-group expert rows
        st = routing_stats_ragged(g_counts, k, tile_rows)
        # actual receiver-side alignment padding, summed over the group
        pad_local = (_round_up(recv_counts, tile_rows).astype(jnp.float32)
                     .sum() - recv_counts.astype(jnp.float32).sum())
        st["moe_padded_rows"] = lax.psum(pad_local, axis_name)
        wire_local = (counts.sum()
                      - jnp.take(counts_mat, me, axis=0).sum())
        st["moe_a2a_wire_rows"] = lax.psum(
            wire_local.astype(jnp.float32), axis_name)
        st["moe_a2a_buffer_rows"] = lax.psum(
            jnp.asarray((n - 1) * chunk_rows, jnp.float32), axis_name)
        return out, aux, st
    return out, aux


def moe_shard_map_dispatch(x, gate_logits, expert_fn, expert_params_local,
                           num_experts, axis_name="ep", k=2,
                           capacity_factor=1.25, strict_capacity=False,
                           return_stats=False):
    """Explicit all-to-all path (inside shard_map over 'ep'): each device owns
    E/ep experts; tokens route via lax.all_to_all, mirroring the reference's
    global_scatter/global_gather."""
    n = _axis_size(axis_name)
    T, D = x.shape  # T = this device's LOCAL tokens
    e_local = num_experts // n
    capacity, ref_cap = moe_capacity(T, k, num_experts, capacity_factor)
    combine, dispatch, aux = top_k_gating(
        gate_logits, k, capacity,
        drop_capacity=ref_cap if strict_capacity else capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)  # [E,C,D]
    # tiled all_to_all: expert axis (owner-major: expert e lives on device
    # e // e_local) splits into n chunks of e_local experts, received chunks
    # concatenate along capacity -> each owner holds its experts' slots from
    # EVERY source device: [e_local, n*C, D]
    with _obs.comm_span("moe.all_to_all_dispatch",
                        nbytes=expert_in.size * expert_in.dtype.itemsize,
                        site="moe.a2a_dispatch"):
        recv = lax.all_to_all(expert_in, axis_name, split_axis=0,
                              concat_axis=1, tiled=True)
    out_local = jax.vmap(expert_fn)(expert_params_local, recv)
    # inverse exchange: capacity splits back per source, experts concat back
    # to the full [E, C, D'] on each source device
    with _obs.comm_span("moe.all_to_all_combine",
                        nbytes=out_local.size * out_local.dtype.itemsize,
                        site="moe.a2a_combine"):
        expert_out = lax.all_to_all(out_local, axis_name, split_axis=1,
                                    concat_axis=0, tiled=True)
    out = jnp.einsum("tec,ecd->td", combine.astype(expert_out.dtype), expert_out)
    if return_stats:
        return out, aux, routing_stats_onehot(
            dispatch, k, drop_capacity=ref_cap if strict_capacity
            else capacity)
    return out, aux
