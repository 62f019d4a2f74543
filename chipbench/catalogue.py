"""Catalogue rows and query rows made on the device from ``--seed``.

Rows are made in blocks: block ``b`` of a stream is a pure function of
``(seed, stream, b)``, so the reference can make any block again, alone,
after the program's state is freed, and get the same bits. The whole
catalogue is one jitted call, in float32, the type it is served in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: independent random streams drawn from one seed: catalogue rows, query
#: rows, arrival times, and the sample of answers the check reads
CATALOGUE, QUERIES, SCHEDULE, SAMPLE = 0, 1, 2, 3


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key for ``stream`` from a seed of up to 64 bits."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def _scale(dist: dict, rank: int) -> jax.Array:
    """Per-column scale of a row distribution.

    ``lowrank_spectrum``: N(0, 1) factors scaled by ``1/sqrt(1 + r)``,
    the decaying spectrum of ``repro.core.random_model``; ``normal``:
    N(0, ``std``^2).
    """
    kind = dist["kind"]
    if kind == "lowrank_spectrum":
        return 1.0 / jnp.sqrt(1.0 + jnp.arange(rank, dtype=jnp.float32))
    if kind == "normal":
        return jnp.full((rank,), float(dist["std"]), jnp.float32)
    raise ValueError(f"unknown row distribution {kind!r}")


def _block(key, b, block_rows: int, rank: int, dist: dict):
    rows = jax.random.normal(jax.random.fold_in(key, b),
                             (block_rows, rank), jnp.float32)
    return rows * _scale(dist, rank)[None, :]


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _all_rows(key, n_rows: int, rank: int, block_rows: int, dist_items):
    dist = dict(dist_items)
    nb = -(-n_rows // block_rows)
    blocks = jax.vmap(lambda b: _block(key, b, block_rows, rank, dist))(
        jnp.arange(nb, dtype=jnp.uint32))
    return blocks.reshape(nb * block_rows, rank)[:n_rows]


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _one_block(key, b, block_rows: int, rank: int, dist_items):
    return _block(key, b, block_rows, rank, dict(dist_items))


def rows(seed: int, stream: int, n_rows: int, rank: int, dist: dict,
         block_rows: int) -> jax.Array:
    """``[n_rows, rank]`` float32 rows of one stream, on the device."""
    return _all_rows(seed_key(seed, stream), int(n_rows), int(rank),
                     int(block_rows), tuple(sorted(dist.items())))


def block(seed: int, stream: int, b: int, rank: int, dist: dict,
          block_rows: int) -> jax.Array:
    """Block ``b`` of a stream alone: rows ``b*block_rows`` onward, the
    same bits :func:`rows` puts there."""
    return _one_block(seed_key(seed, stream), jnp.uint32(b),
                      int(block_rows), int(rank),
                      tuple(sorted(dist.items())))


def n_blocks(n_rows: int, block_rows: int) -> int:
    return -(-int(n_rows) // int(block_rows))


def host_rows(seed: int, stream: int, n_rows: int, rank: int, dist: dict,
              block_rows: int) -> np.ndarray:
    """Query rows for the client, made on the device, read to the host."""
    return np.asarray(rows(seed, stream, n_rows, rank, dist, block_rows))
