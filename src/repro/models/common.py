"""Shared model plumbing: sharding constraints, norms, init, activations.

Sharding is expressed through *logical axis names* resolved against the
ambient mesh. When no mesh is active (single-device tests) every constraint
is a no-op, so the same model code runs in smoke tests and in the 512-chip
dry-run unchanged.

Logical axes (DESIGN.md §5):
  "dp"     — batch / data parallel (mesh: ("pod", "data") when multi-pod)
  "tp"     — tensor parallel / expert parallel / vocab shard (mesh: "model")
  "fsdp"   — parameter FSDP shard (mesh: "data")
  "sp"     — sequence parallel for the residual stream (mesh: "model")
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jnp.ndarray
PyTree = Any


@dataclasses.dataclass(frozen=True)
class MeshRules:
    """Maps logical axis names to mesh axis names (or None = replicate)."""

    dp: Union[str, Tuple[str, ...], None] = ("pod", "data")
    tp: Optional[str] = "model"
    fsdp: Optional[str] = "data"
    sp: Optional[str] = "model"

    def resolve(self, *logical: Optional[str]) -> P:
        """Translate logical names into a PartitionSpec for the ambient mesh."""
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return P()
        names = set(mesh.axis_names)

        def r(ax):
            if ax is None:
                return None
            got = getattr(self, ax)
            if got is None:
                return None
            if isinstance(got, tuple):
                sub = tuple(g for g in got if g in names)
                return sub if sub else None
            return got if got in names else None

        return P(*(r(ax) for ax in logical))


# Single-pod rules drop the "pod" axis automatically via resolve().
DEFAULT_RULES = MeshRules()


def shard(x: Array, rules: MeshRules, *logical: Optional[str]) -> Array:
    """with_sharding_constraint against logical axes; no-op without a mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, rules.resolve(*logical))


# ---------------------------------------------------------------------------
# Initialisers / numerics
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis: int = -2) -> Array:
    """LeCun-normal (fan-in) init in fp32."""
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape, dtype=jnp.float32)
            / jnp.sqrt(jnp.asarray(fan_in, jnp.float32)))


def embed_init(key, shape, scale: float = 1.0) -> Array:
    return jax.random.normal(key, shape, dtype=jnp.float32) * scale


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
}


def count_params(params: PyTree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params)
               if hasattr(x, "size"))


def cast_tree(params: PyTree, dtype) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params)


def mlp_params(key, dims: Sequence[int], bias: bool = True):
    """Plain MLP parameter stack for recsys/GNN towers."""
    keys = jax.random.split(key, len(dims) - 1)
    layers = []
    for i, k in enumerate(keys):
        p = {"w": dense_init(k, (dims[i], dims[i + 1]))}
        if bias:
            p["b"] = jnp.zeros((dims[i + 1],), jnp.float32)
        layers.append(p)
    return layers


def mlp_apply(layers, x: Array, act: str = "relu", final_act: bool = False) -> Array:
    fn = ACTIVATIONS[act]
    n = len(layers)
    for i, p in enumerate(layers):
        x = x @ p["w"].astype(x.dtype)
        if "b" in p:
            x = x + p["b"].astype(x.dtype)
        if i + 1 < n or final_act:
            x = fn(x)
    return x
