"""Mixture-of-Experts FFN with sort-based token dispatch (EP-shardable).

Dispatch uses the argsort formulation (MegaBlocks-style, DESIGN.md §5):
flatten (token, expert) assignments, sort by expert, compute each
assignment's position within its expert group, scatter into a fixed
[E, capacity, D] buffer, run one batched expert GEMM, and combine with
gate-weighted segment-sum. Everything is static-shaped: tokens beyond an
expert's capacity are dropped (classic Switch behaviour) and counted in
aux stats. Sharding: tokens over "dp", experts over "tp" — the scatter
between those two layouts is the MoE all-to-all.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ACTIVATIONS, MeshRules, dense_init, shard

Array = jnp.ndarray


class MoEParams(NamedTuple):
    router: Array   # [D, E]
    w_gate: Array   # [E, D, F]
    w_up: Array     # [E, D, F]
    w_down: Array   # [E, F, D]


def init_moe(key, d_model: int, d_ff: int, n_experts: int) -> MoEParams:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return MoEParams(
        router=dense_init(k1, (d_model, n_experts)),
        w_gate=dense_init(k2, (n_experts, d_model, d_ff), in_axis=-2),
        w_up=dense_init(k3, (n_experts, d_model, d_ff), in_axis=-2),
        w_down=dense_init(k4, (n_experts, d_ff, d_model), in_axis=-2),
    )


def moe_ffn(
    params: MoEParams,
    x: Array,                     # [T, D] flattened tokens
    top_k: int,
    capacity_factor: float = 1.25,
    act: str = "silu",
    rules: MeshRules = MeshRules(),
) -> Tuple[Array, dict]:
    """Returns (output [T, D], aux dict with load-balance loss + drop rate)."""
    T, D = x.shape
    E = params.router.shape[1]
    fn = ACTIVATIONS[act]
    capacity = max(int(T * top_k * capacity_factor / E), 1)
    # round capacity to a lane-friendly multiple
    capacity = -(-capacity // 8) * 8

    logits = x.astype(jnp.float32) @ params.router                # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)           # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # --- aux load-balance loss (Switch eq. 4) -----------------------------
    me = jnp.mean(probs, axis=0)                                  # [E]
    one_hot = jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)
    aux_loss = E * jnp.sum(me * ce)

    # --- sort-based dispatch ----------------------------------------------
    flat_e = expert_ids.reshape(-1)                               # [T*k]
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)
    flat_g = gate_vals.reshape(-1)
    sort_idx = jnp.argsort(flat_e, stable=True)
    se = flat_e[sort_idx]
    st = flat_t[sort_idx]
    sg = flat_g[sort_idx]
    group_start = jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype))
    pos = jnp.arange(T * top_k, dtype=jnp.int32) - group_start[se]
    keep = pos < capacity
    dst = jnp.where(keep, se * capacity + pos, E * capacity)      # drop slot

    buf = jnp.zeros((E * capacity + 1, D), x.dtype)
    buf = buf.at[dst].set(x[st] * keep[:, None].astype(x.dtype))
    buf = buf[: E * capacity].reshape(E, capacity, D)
    buf = shard(buf, rules, "tp", None, None)

    # --- batched expert GEMMs ----------------------------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, params.w_gate.astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, params.w_up.astype(buf.dtype))
    h = fn(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, params.w_down.astype(buf.dtype))
    y = shard(y, rules, "tp", None, None)

    # --- combine ------------------------------------------------------------
    y_flat = y.reshape(E * capacity, D)
    contrib = jnp.where(keep[:, None], y_flat[jnp.minimum(dst, E * capacity - 1)],
                        0.0) * sg[:, None].astype(y_flat.dtype)
    out = jax.ops.segment_sum(contrib, st, num_segments=T)
    drop_rate = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return out.astype(x.dtype), {"aux_loss": aux_loss, "drop_rate": drop_rate}


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map) — §Perf hillclimb A
# ---------------------------------------------------------------------------


def ep_available(n_experts: int, rules: MeshRules) -> bool:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or rules.tp not in mesh.axis_names:
        return False
    return n_experts % dict(mesh.shape)[rules.tp] == 0


def moe_ffn_ep(
    params: MoEParams,
    h: Array,                     # [B, S, D] residual-layout activations
    top_k: int,
    capacity_factor: float = 1.25,
    act: str = "silu",
    rules: MeshRules = MeshRules(),
) -> Tuple[Array, dict]:
    """Expert-parallel MoE via shard_map (DESIGN/EXPERIMENTS §Perf-A).

    The pjit global dispatch (argsort over ALL tokens + scatter into a
    tp-sharded buffer) makes XLA reshard token payloads repeatedly —
    measured 2.0e15 collective bytes/step on olmoe train_4k. Here instead:

    * activations enter replicated over tp within each dp row
      (in_spec P(dp, -, -); one [T_loc, D] all-gather per layer),
    * every device routes its dp-row's tokens LOCALLY and builds the
      capacity buffer only for ITS E/tp experts (no token exchange),
    * local expert GEMMs,
    * combine = one bf16 psum over tp (each token's top-k experts live on
      disjoint shards).

    Capacity is per (dp-row, expert) — GShard-style local capacity.
    """
    mesh = jax.sharding.get_abstract_mesh()
    tp = rules.tp
    sizes = dict(mesh.shape)
    tp_size = sizes[tp]
    dp = tuple(a for a in (rules.dp if isinstance(rules.dp, tuple)
                           else (rules.dp,)) if a in mesh.axis_names)
    dp_size = 1
    for a in dp:
        dp_size *= sizes[a]
    if h.shape[0] % max(dp_size, 1) != 0:
        dp = ()   # tiny decode batches: tokens replicated, experts still EP
    E = params.router.shape[1]
    E_local = E // tp_size
    fn = ACTIVATIONS[act]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, None), P(tp, None, None), P(tp, None, None),
                  P(tp, None, None), P(dp if dp else None, None, None)),
        out_specs=(P(dp if dp else None, None, None), P(), P()),
        check_vma=False,
    )
    def _local(router, w_gate, w_up, w_down, h_l):
        Bl, S, D = h_l.shape
        T_l = Bl * S
        x = h_l.reshape(T_l, D)
        capacity = max(int(T_l * top_k * capacity_factor / E), 1)
        capacity = -(-capacity // 8) * 8

        # route in the compute dtype: upcasting x to f32 here makes XLA
        # hoist the convert BEFORE the boundary all-gather, doubling every
        # activation collective (§Perf-A iter 3). The [T_l, E] logits are
        # tiny — upcast those instead.
        logits = (x @ router.astype(x.dtype)).astype(jnp.float32)  # [T_l, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32),
                      axis=0)
        aux = E * jnp.sum(me * ce)

        e_first = jax.lax.axis_index(tp) * E_local
        flat_e = expert_ids.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T_l, dtype=jnp.int32), top_k)
        flat_g = gate_vals.reshape(-1)
        local_e = flat_e - e_first
        is_local = jnp.logical_and(local_e >= 0, local_e < E_local)
        le = jnp.where(is_local, local_e, E_local)            # dump bucket
        sort_idx = jnp.argsort(le, stable=True)
        se = le[sort_idx]
        st_tok = flat_t[sort_idx]
        sg = flat_g[sort_idx]
        group_start = jnp.searchsorted(se, jnp.arange(E_local, dtype=se.dtype))
        pos = jnp.arange(T_l * top_k, dtype=jnp.int32) - group_start[se]
        keep = jnp.logical_and(se < E_local, pos < capacity)
        dst = jnp.where(keep, se * capacity + pos, E_local * capacity)

        # §Perf-A iter 2: scatter token INDICES (4 bytes/slot) and gate
        # values into the capacity layout, then gather only the
        # E_local*capacity rows actually computed — never materialising
        # the [T_l*top_k, D] token payload the naive formulation reads.
        n_slots = E_local * capacity
        tok_buf = jnp.full((n_slots + 1,), T_l, jnp.int32).at[dst].set(st_tok)
        gate_buf = jnp.zeros((n_slots + 1,), jnp.float32).at[dst].set(
            sg * keep.astype(jnp.float32))
        tok_buf = tok_buf[:n_slots]
        gate_buf = gate_buf[:n_slots]
        valid = (tok_buf < T_l).astype(x.dtype)[:, None]
        buf = (x[jnp.minimum(tok_buf, T_l - 1)] * valid
               ).reshape(E_local, capacity, D)

        g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(buf.dtype))
        y = jnp.einsum("ecf,efd->ecd", fn(g) * u, w_down.astype(buf.dtype))

        y_flat = y.reshape(n_slots, D) * gate_buf[:, None].astype(x.dtype)
        partial = jax.ops.segment_sum(y_flat * valid, tok_buf,
                                      num_segments=T_l + 1)[:T_l]
        out = jax.lax.psum(partial.astype(h_l.dtype), tp)     # combine experts

        denom = 1.0
        for a in dp:
            aux = jax.lax.psum(aux, a)
            denom *= jax.lax.axis_size(a)
        # each tp shard keeps a disjoint subset of the T_l*top_k assignments
        kept = jax.lax.psum(jnp.mean(keep.astype(jnp.float32)), tp)
        drop = 1.0 - kept
        for a in dp:
            drop = jax.lax.psum(drop, a)
        return out.reshape(Bl, S, D), aux / denom, drop / denom

    out, aux_loss, drop_rate = _local(params.router, params.w_gate,
                                      params.w_up, params.w_down, h)
    return out, {"aux_loss": aux_loss, "drop_rate": drop_rate}
