"""Catalogue rows and query rows made on the device from ``--seed``.

Rows are made in blocks: block ``b`` of a stream is a pure function of
``(seed, stream, b)``, so the reference can make any block again, alone,
after the program's state is freed, and get the same bits. The whole
catalogue is one jitted call, in float32, the type it is served in. On
one chip it lands on the default device; over several it is sharded by
rows, and each chip makes only its own blocks, one at a time, into its
shard, so no chip ever holds more than its share and one block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

#: independent random streams drawn from one seed: catalogue rows, query
#: rows, arrival times, and the sample of answers the check reads
CATALOGUE, QUERIES, SCHEDULE, SAMPLE = 0, 1, 2, 3
#: the sub-stream of a block's key that draws its rows' norm factors
NORMS = 1
#: the mesh axis a sharded catalogue's rows are split over
ROW_AXIS = "data"


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


def _rows(bkey, block_rows: int, rank: int, dist: dict):
    """One block's rows from its own key.

    ``lognormal_norms`` (``{"sigma": s, "base": {...}}``): the rows of
    the ``base`` kind, from the same key and so with the same bits, each
    scaled by ``exp(s * z_row)``, ``z_row`` ~ N(0, 1) from the block's
    ``NORMS`` sub-stream: row norms that spread as popularity does.
    """
    if dist["kind"] == "lognormal_norms":
        z = jax.random.normal(jax.random.fold_in(bkey, NORMS), (block_rows,),
                              jnp.float32)
        return (_rows(bkey, block_rows, rank, dist["base"])
                * jnp.exp(float(dist["sigma"]) * z)[:, None])
    rows = jax.random.normal(bkey, (block_rows, rank), jnp.float32)
    return rows * _scale(dist, rank)[None, :]


def _block(key, b, block_rows: int, rank: int, dist: dict):
    return _rows(jax.random.fold_in(key, b), block_rows, rank, dist)


def _freeze(dist: dict) -> tuple:
    """A distribution as a static (hashable) jit argument; a flat one
    freezes to its sorted items."""
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v)
                        for k, v in dist.items()))


def _thaw(items: tuple) -> dict:
    return {k: _thaw(v) if isinstance(v, tuple) else v for k, v in items}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _all_rows(key, n_rows: int, rank: int, block_rows: int, dist_items):
    dist = _thaw(dist_items)
    nb = -(-n_rows // block_rows)
    blocks = jax.vmap(lambda b: _block(key, b, block_rows, rank, dist))(
        jnp.arange(nb, dtype=jnp.uint32))
    return blocks.reshape(nb * block_rows, rank)[:n_rows]


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _one_block(key, b, block_rows: int, rank: int, dist_items):
    return _block(key, b, block_rows, rank, _thaw(dist_items))


@functools.lru_cache(maxsize=None)
def _sharded_maker(mesh: Mesh, shard_rows: int, rank: int, block_rows: int,
                   dist_items):
    """A jitted ``key -> [chips * shard_rows, rank]`` that shards rows
    over ``mesh``: chip ``c`` makes blocks ``c * nb`` to ``c * nb + nb -
    1`` in a loop, each written into its shard in place."""
    dist = _thaw(dist_items)
    nb = shard_rows // block_rows

    def shard(key):
        b0 = jax.lax.axis_index(ROW_AXIS).astype(jnp.uint32) * nb

        def put(i, out):
            blk = _block(key, b0 + i.astype(jnp.uint32), block_rows, rank,
                         dist)
            return jax.lax.dynamic_update_slice(out, blk, (i * block_rows, 0))

        return jax.lax.fori_loop(0, nb, put,
                                 jnp.zeros((shard_rows, rank), jnp.float32))

    return jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=P(),
                                 out_specs=P(ROW_AXIS, None),
                                 check_vma=False))


def row_mesh(chips: int) -> Mesh:
    """The first ``chips`` devices on one ``ROW_AXIS``."""
    devices = jax.devices()
    if len(devices) < chips:
        raise ValueError(f"{chips} chips asked for, JAX has {len(devices)}")
    return Mesh(np.asarray(devices[:chips]), (ROW_AXIS,))


def rows(seed: int, stream: int, n_rows: int, rank: int, dist: dict,
         block_rows: int, chips: int = 1) -> jax.Array:
    """``[n_rows, rank]`` float32 rows of one stream, on the device; with
    ``chips > 1`` sharded by rows over the first ``chips`` devices
    (``NamedSharding(row_mesh(chips), P(ROW_AXIS, None))``), in shards
    of equal whole blocks."""
    key = seed_key(seed, stream)
    n_rows, rank = int(n_rows), int(rank)
    block_rows, chips = int(block_rows), int(chips)
    if chips == 1:
        return _all_rows(key, n_rows, rank, block_rows, _freeze(dist))
    if n_rows % (chips * block_rows):
        raise ValueError(
            f"{n_rows} rows do not split into {chips} shards of whole "
            f"{block_rows}-row blocks: a sharded catalogue's rows must be a "
            f"multiple of chips x block_rows = {chips * block_rows}")
    return _sharded_maker(row_mesh(chips), n_rows // chips, rank,
                          block_rows, _freeze(dist))(key)


def block(seed: int, stream: int, b: int, rank: int, dist: dict,
          block_rows: int) -> jax.Array:
    """Block ``b`` of a stream alone: rows ``b*block_rows`` onward, the
    same bits :func:`rows` puts there."""
    return _one_block(seed_key(seed, stream), jnp.uint32(b),
                      int(block_rows), int(rank), _freeze(dist))


def n_blocks(n_rows: int, block_rows: int) -> int:
    return -(-int(n_rows) // int(block_rows))


def host_rows(seed: int, stream: int, n_rows: int, rank: int, dist: dict,
              block_rows: int) -> np.ndarray:
    """Query rows for the client, made on the device, read to the host."""
    return np.asarray(rows(seed, stream, n_rows, rank, dist, block_rows))
