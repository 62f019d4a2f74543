"""Decoder-only LM (dense + MoE) with pod-scale sharding annotations.

Implementation notes (DESIGN.md §5):
* ``lax.scan`` over stacked layer params — HLO size is O(1) in depth
  (deepseek-67b has 95 layers; unrolled HLO would not compile in reasonable
  time at mesh 512).
* Megatron-style TP + sequence parallelism: the residual stream lives
  sequence-sharded P(dp, sp, -); attention/FFN inner activations live
  head-/ff-sharded P(dp, -, tp). XLA inserts the all-gather /
  reduce-scatter pairs at the constraint boundaries.
* Per-layer remat (``jax.checkpoint``) — only layer-boundary residuals are
  stored; internals recompute in backward.
* Chunked cross-entropy: logits are never materialised at [B, S, V];
  a scan over sequence chunks bounds peak memory at [B, chunk, V].
* Decode: KV caches stacked [L, B, S, Hkv, hd], sequence-shardable for
  long contexts (long_500k runs as decode; linear in context).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.attention import apply_rope, blocked_attention, decode_attention
from repro.models.common import (
    ACTIVATIONS,
    MeshRules,
    dense_init,
    embed_init,
    rms_norm,
    shard,
)
from repro.models.moe import MoEParams, ep_available, init_moe, moe_ffn, moe_ffn_ep

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    act: str = "silu"
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # MoE
    moe: bool = False
    n_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    moe_ep: bool = True   # shard_map expert-parallel dispatch (§Perf-A)
    # numerics / memory
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    logit_chunk: int = 512
    kv_block: int = 512
    # roofline-calibration mode: unroll every scan so cost_analysis counts
    # loop bodies exactly (XLA counts a while body ONCE; see DESIGN.md §8)
    unroll: bool = False

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + layers + unembed)."""
        d, l = self.d_model, self.n_layers
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.moe:
            ffn = d * self.n_experts + 3 * self.n_experts * d * self.moe_d_ff
        else:
            ffn = 3 * d * self.d_ff
        norms = 2 * d
        return (self.vocab_size * d                      # embed
                + l * (attn + ffn + norms)
                + d                                       # final norm
                + d * self.vocab_size)                    # unembed

    def active_param_count(self) -> int:
        """Active-per-token params (MoE: only routed experts)."""
        if not self.moe:
            return self.param_count()
        d, l = self.d_model, self.n_layers
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        ffn = d * self.n_experts + 3 * self.moe_top_k * d * self.moe_d_ff
        return (self.vocab_size * d + l * (attn + ffn + 2 * d)
                + d + d * self.vocab_size)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(config: TransformerConfig, key) -> Dict:
    keys = jax.random.split(key, 12)
    L, D = config.n_layers, config.d_model
    layers = {
        "ln1": jnp.zeros((L, D), jnp.float32),
        "ln2": jnp.zeros((L, D), jnp.float32),
        "wq": dense_init(keys[0], (L, D, config.q_dim)),
        "wk": dense_init(keys[1], (L, D, config.kv_dim)),
        "wv": dense_init(keys[2], (L, D, config.kv_dim)),
        "wo": dense_init(keys[3], (L, config.q_dim, D)),
    }
    if config.moe:
        layers["router"] = dense_init(keys[4], (L, D, config.n_experts))
        layers["moe_gate"] = dense_init(keys[5], (L, config.n_experts, D, config.moe_d_ff))
        layers["moe_up"] = dense_init(keys[6], (L, config.n_experts, D, config.moe_d_ff))
        layers["moe_down"] = dense_init(keys[7], (L, config.n_experts, config.moe_d_ff, D))
    else:
        layers["w_gate"] = dense_init(keys[4], (L, D, config.d_ff))
        layers["w_up"] = dense_init(keys[5], (L, D, config.d_ff))
        layers["w_down"] = dense_init(keys[6], (L, config.d_ff, D))
    return {
        "embed": embed_init(keys[8], (config.vocab_size, D)),
        "layers": layers,
        "final_norm": jnp.zeros((D,), jnp.float32),
        "unembed": dense_init(keys[9], (D, config.vocab_size)),
    }


def _div(n: int, mesh_axis: Optional[str]) -> bool:
    """True if dim n is divisible by the ambient mesh axis size."""
    if mesh_axis is None:
        return False
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh_axis not in mesh.axis_names:
        return False
    return n % dict(mesh.shape)[mesh_axis] == 0


def param_specs(config: TransformerConfig, rules: MeshRules,
                mode: str = "train") -> Dict:
    """PartitionSpec tree matching init_params. mode 'serve' drops FSDP
    (batch owns the data axis exclusively at inference)."""
    tp = rules.tp
    fsdp = rules.fsdp if mode == "train" else None
    layers = {
        "ln1": P(None, None),
        "ln2": P(None, None),
        "wq": P(None, fsdp, tp),
        "wk": P(None, fsdp, tp),
        "wv": P(None, fsdp, tp),
        "wo": P(None, tp, fsdp),
    }
    if config.moe:
        layers["router"] = P(None, fsdp, None)
        layers["moe_gate"] = P(None, tp, fsdp, None)
        layers["moe_up"] = P(None, tp, fsdp, None)
        layers["moe_down"] = P(None, tp, None, fsdp)
    else:
        layers["w_gate"] = P(None, fsdp, tp)
        layers["w_up"] = P(None, fsdp, tp)
        layers["w_down"] = P(None, tp, fsdp)
    return {
        "embed": P(tp, fsdp),
        "layers": layers,
        "final_norm": P(None),
        "unembed": P(fsdp, tp),
    }


# ---------------------------------------------------------------------------
# Layer
# ---------------------------------------------------------------------------


def _attention_block(lp: Dict, x: Array, config: TransformerConfig,
                     rules: MeshRules, positions: Array,
                     kv_cache: Optional[Tuple[Array, Array]] = None,
                     cache_len: Optional[Array] = None):
    """x: [B, S, D] (residual layout). Returns (out [B,S,D], new_kv)."""
    B, S, D = x.shape
    dt = config.compute_dtype
    h = rms_norm(x, lp["ln1"], config.norm_eps)
    # qkv projections — inner layout: heads sharded, sequence gathered
    q = (h @ lp["wq"].astype(dt)).reshape(B, S, config.n_heads, config.head_dim)
    k = (h @ lp["wk"].astype(dt)).reshape(B, S, config.n_kv_heads, config.head_dim)
    v = (h @ lp["wv"].astype(dt)).reshape(B, S, config.n_kv_heads, config.head_dim)
    if _div(config.n_heads, rules.tp):
        q = shard(q, rules, "dp", None, "tp", None)
    if _div(config.n_kv_heads, rules.tp):
        k = shard(k, rules, "dp", None, "tp", None)
        v = shard(v, rules, "dp", None, "tp", None)
    q = apply_rope(q, positions, config.rope_theta)
    k = apply_rope(k, positions, config.rope_theta)

    new_kv = None
    if kv_cache is not None:
        k_cache, v_cache = kv_cache
        # append at cache_len (batch-uniform position); S == 1 in decode
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), cache_len, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), cache_len, axis=1)
        new_kv = (k_cache, v_cache)
        valid = jnp.full((B,), cache_len + S, jnp.int32)
        # scores stay sharded exactly like the cache's seq axis
        cache_spec = kv_cache_specs(config, rules, B, k_cache.shape[1])["k"]
        score_spec = P(cache_spec[1], None, None, cache_spec[2])
        mesh = jax.sharding.get_abstract_mesh()

        def seq_shard(s):
            if mesh.empty:
                return s
            return jax.lax.with_sharding_constraint(s, score_spec)

        attn = decode_attention(
            q, k_cache.astype(dt), v_cache.astype(dt), cache_len=valid,
            seq_shard=seq_shard)
    else:
        attn = blocked_attention(q, k, v, causal=True, kv_block=config.kv_block,
                                 q_positions=positions, kv_positions=positions,
                                 unroll=config.unroll)
    attn = attn.reshape(B, S, config.q_dim)
    out = attn @ lp["wo"].astype(dt)
    return out, new_kv


def _ffn_block(lp: Dict, x: Array, config: TransformerConfig, rules: MeshRules):
    """Returns (out [B,S,D], aux_loss scalar)."""
    B, S, D = x.shape
    dt = config.compute_dtype
    h = rms_norm(x, lp["ln2"], config.norm_eps)
    if config.moe:
        params = MoEParams(router=lp["router"], w_gate=lp["moe_gate"],
                           w_up=lp["moe_up"], w_down=lp["moe_down"])
        if config.moe_ep and ep_available(config.n_experts, rules):
            out, aux = moe_ffn_ep(params, h, config.moe_top_k,
                                  config.capacity_factor, config.act, rules)
            return out, aux["aux_loss"]
        flat = h.reshape(B * S, D)
        out, aux = moe_ffn(params, flat, config.moe_top_k,
                           config.capacity_factor, config.act, rules)
        return out.reshape(B, S, D), aux["aux_loss"]
    act = ACTIVATIONS[config.act]
    g = h @ lp["w_gate"].astype(dt)
    u = h @ lp["w_up"].astype(dt)
    g = shard(g, rules, "dp", None, "tp")
    out = (act(g) * u) @ lp["w_down"].astype(dt)
    return out, jnp.float32(0.0)


def _layer(lp: Dict, x: Array, config: TransformerConfig, rules: MeshRules,
           positions: Array, kv_cache=None, cache_len=None):
    residual_spec = ("dp", "sp", None) if x.shape[1] > 1 else ("dp", None, None)
    attn_out, new_kv = _attention_block(lp, x, config, rules, positions,
                                        kv_cache, cache_len)
    x = shard(x + attn_out, rules, *residual_spec)
    ffn_out, aux = _ffn_block(lp, x, config, rules)
    x = shard(x + ffn_out, rules, *residual_spec)
    return x, new_kv, aux


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def forward(params: Dict, tokens: Array, config: TransformerConfig,
            rules: MeshRules = MeshRules()) -> Tuple[Array, Array]:
    """Training/prefill forward. tokens: [B, S] -> (hidden [B,S,D], aux)."""
    B, S = tokens.shape
    dt = config.compute_dtype
    x = params["embed"].astype(dt)[tokens]
    x = shard(x, rules, "dp", "sp", None)
    positions = jnp.arange(S)

    def body(carry, lp):
        x, aux = carry
        y, _, a = _layer(lp, x, config, rules, positions)
        return (y, aux + a), None

    body_fn = jax.checkpoint(body) if config.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.float32(0.0)),
                               params["layers"],
                               unroll=True if config.unroll else 1)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return x, aux


def prefill(params: Dict, tokens: Array, config: TransformerConfig,
            rules: MeshRules = MeshRules(), cache_dtype=jnp.bfloat16):
    """Prompt ingestion: forward pass that also emits the stacked KV cache
    ({k, v}: [L, B, S, Hkv, hd]) plus last-position hidden states."""
    B, S = tokens.shape
    dt = config.compute_dtype
    x = params["embed"].astype(dt)[tokens]
    x = shard(x, rules, "dp", "sp", None)
    positions = jnp.arange(S)

    def body(x, lp):
        h = rms_norm(x, lp["ln1"], config.norm_eps)
        q = (h @ lp["wq"].astype(dt)).reshape(B, S, config.n_heads, config.head_dim)
        k = (h @ lp["wk"].astype(dt)).reshape(B, S, config.n_kv_heads, config.head_dim)
        v = (h @ lp["wv"].astype(dt)).reshape(B, S, config.n_kv_heads, config.head_dim)
        q = apply_rope(q, positions, config.rope_theta)
        k = apply_rope(k, positions, config.rope_theta)
        attn = blocked_attention(q, k, v, causal=True, kv_block=config.kv_block,
                                 q_positions=positions, kv_positions=positions,
                                 unroll=config.unroll)
        x = x + attn.reshape(B, S, config.q_dim) @ lp["wo"].astype(dt)
        ffn_out, _ = _ffn_block(lp, x, config, rules)
        x = shard(x + ffn_out, rules, "dp", "sp", None)
        return x, (k.astype(cache_dtype), v.astype(cache_dtype))

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"],
                               unroll=True if config.unroll else 1)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return x[:, -1, :], {"k": ks, "v": vs}


def logits_from_hidden(params: Dict, hidden: Array,
                       config: TransformerConfig) -> Array:
    return hidden @ params["unembed"].astype(hidden.dtype)


def chunked_xent(params: Dict, hidden: Array, labels: Array,
                 config: TransformerConfig, rules: MeshRules) -> Array:
    """Cross-entropy without materialising [B, S, V] logits.

    Scans sequence chunks; each chunk computes its own logits + logsumexp
    and is rematted, so peak memory is [B, chunk, V / tp].
    """
    B, S, D = hidden.shape
    chunk = min(config.logit_chunk, S)
    n_chunks = S // chunk if S % chunk == 0 else 1
    if S % chunk != 0:
        chunk = S
    hc = hidden.reshape(B, n_chunks, chunk, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    w = params["unembed"]

    V = w.shape[1]

    @jax.checkpoint
    def one_chunk(carry, xs):
        h, y = xs
        logits = (h @ w.astype(h.dtype)).astype(jnp.float32)
        logits = shard(logits, rules, "dp", None, "tp")
        lse = jax.nn.logsumexp(logits, axis=-1)
        # gold logit via one-hot contraction: partitions over the sharded
        # vocab axis as a local partial + psum; take_along_axis would
        # all-gather the [B, chunk, V] logits (67 GB/step at gemma scale —
        # found via the collective-bytes audit, see EXPERIMENTS.md §Perf).
        onehot = jax.nn.one_hot(y, V, dtype=logits.dtype)
        gold = jnp.einsum("bcv,bcv->bc", logits, onehot)
        return carry + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(one_chunk, jnp.float32(0.0), (hc, lc),
                            unroll=True if config.unroll else 1)
    return total / (B * S)


def loss_fn(params: Dict, batch: Dict, config: TransformerConfig,
            rules: MeshRules = MeshRules()) -> Tuple[Array, Dict]:
    hidden, aux = forward(params, batch["tokens"], config, rules)
    xent = chunked_xent(params, hidden, batch["labels"], config, rules)
    loss = xent + config.aux_loss_weight * aux / max(config.n_layers, 1)
    return loss, {"xent": xent, "aux_loss": aux}


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------


def init_kv_cache(config: TransformerConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> Dict:
    shape = (config.n_layers, batch, max_len, config.n_kv_heads, config.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_specs(config: TransformerConfig, rules: MeshRules,
                   batch: int, seq_len: int) -> Dict:
    """Shard cache over batch (dp) and sequence (sp) where divisible.

    §Perf-B iter 3: when the batch cannot occupy the data axis (e.g.
    long_500k's batch=1), the SEQUENCE takes it instead — 256-way context
    parallelism (data x model) instead of 16-way, cutting both the
    per-device cache slice and the per-token attention reads 16x.
    """
    mesh = jax.sharding.get_abstract_mesh()
    dp = None
    sp = None
    if not mesh.empty:
        sizes = dict(mesh.shape)
        dp_axes = rules.dp if isinstance(rules.dp, tuple) else (rules.dp,)
        dp_axes = tuple(a for a in dp_axes if a in sizes)
        dp_size = 1
        for a in dp_axes:
            dp_size *= sizes[a]
        dp = dp_axes if (dp_axes and batch % dp_size == 0) else None
        seq_axes = tuple(a for a in ((rules.sp,) if rules.sp in sizes else ())
                         if a in sizes)
        if dp is None and dp_axes:
            seq_axes = dp_axes + tuple(a for a in seq_axes if a not in dp_axes)
        seq_size = 1
        for a in seq_axes:
            seq_size *= sizes[a]
        sp = seq_axes if (seq_axes and seq_len % seq_size == 0) else None
    spec = P(None, dp, sp, None, None)
    return {"k": spec, "v": spec}


def serve_step(params: Dict, cache: Dict, tokens: Array, cache_len,
               config: TransformerConfig, rules: MeshRules = MeshRules(),
               top_k: int = 0):
    """One decode step. tokens: [B, 1]. Returns (logits-or-topk, new cache).

    ``top_k > 0`` routes the logit head through the sharded exact top-K
    merge (the paper's technique as the LM sampling head).
    """
    B, S = tokens.shape
    dt = config.compute_dtype
    x = params["embed"].astype(dt)[tokens]
    x = shard(x, rules, "dp", None, None)
    positions = cache_len + jnp.arange(S)

    def body(carry, xs):
        x = carry
        lp, kc, vc = xs
        y, new_kv, _ = _layer(lp, x, config, rules, positions,
                              kv_cache=(kc, vc), cache_len=cache_len)
        return y, new_kv

    x, new_kv = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]),
                             unroll=True if config.unroll else 1)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    hidden = x[:, -1, :]                                   # [B, D]
    new_cache = {"k": new_kv[0], "v": new_kv[1]}
    if top_k <= 0:
        logits = hidden @ params["unembed"].astype(dt)
        return logits, new_cache
    vals, idx = topk_logits(hidden, params["unembed"], top_k, rules)
    return (vals, idx), new_cache


def topk_logits(hidden: Array, unembed: Array, k: int,
                rules: MeshRules = MeshRules()):
    """Exact top-K over the vocab — the SEP-LR head (DESIGN.md §3).

    With the vocab tp-sharded this is the distributed merge of
    ``repro.core.sharded``: local matmul + local top-K, all-gather only
    ``K`` candidates per shard. Without a mesh it degrades to naive.
    """
    mesh = jax.sharding.get_abstract_mesh()
    tp = rules.tp
    if mesh.empty or tp not in mesh.axis_names \
            or unembed.shape[1] % dict(mesh.shape)[tp] != 0:
        logits = hidden.astype(jnp.float32) @ unembed.astype(jnp.float32)
        return jax.lax.top_k(logits, k)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(None, tp)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def _local(h, w_local):
        v_local = w_local.shape[1]
        logits = h.astype(jnp.float32) @ w_local.astype(jnp.float32)
        vals, idx = jax.lax.top_k(logits, min(k, v_local))
        idx = idx + jax.lax.axis_index(tp) * v_local
        vals = jax.lax.all_gather(vals, tp, axis=1, tiled=True)
        idx = jax.lax.all_gather(idx, tp, axis=1, tiled=True)
        fvals, fpos = jax.lax.top_k(vals, k)
        return fvals, jnp.take_along_axis(idx, fpos, axis=1)

    return _local(hidden, unembed)
