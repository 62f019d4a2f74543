"""The catalogue made from the seed: one chip as before, sharded by rows
over several, and the norm-skewed row kind.

The sharded cases run in a child process on four virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``), so that this
process keeps its one-device view.
"""

import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import catalogue, spec

ROOT = spec.ROOT
KINDS = [{"kind": "lowrank_spectrum"}, {"kind": "normal", "std": 0.02},
         {"kind": "lognormal_norms", "sigma": 1.5,
          "base": {"kind": "lowrank_spectrum"}}]


def _on_four_devices(code: str) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=ROOT)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


@pytest.mark.parametrize("dist", KINDS, ids=lambda d: d["kind"])
def test_sharded_rows_are_the_blocks_bit_for_bit(dist):
    _on_four_devices(f"""
        import numpy as np
        from chipbench import catalogue
        dist = {dist!r}
        seed, n, r, br = 2**41 + 9, 8 * 512, 24, 512
        got = catalogue.rows(seed, catalogue.CATALOGUE, n, r, dist, br,
                             chips=4)
        assert got.shape == (n, r) and got.dtype == np.float32
        want = np.concatenate([
            np.asarray(catalogue.block(seed, catalogue.CATALOGUE, b, r,
                                       dist, br)) for b in range(8)])
        np.testing.assert_array_equal(np.asarray(got), want)
    """)


def test_each_chip_holds_its_own_row_range_and_nothing_is_gathered():
    _on_four_devices("""
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from chipbench import catalogue
        dist = {"kind": "lowrank_spectrum"}
        seed, n, r, br = 7, 16 * 256, 16, 256
        got = catalogue.rows(seed, catalogue.CATALOGUE, n, r, dist, br,
                             chips=4)
        mesh = catalogue.row_mesh(4)
        assert got.sharding.is_equivalent_to(
            NamedSharding(mesh, P("data", None)), 2)
        per = n // 4
        for shard in got.addressable_shards:
            c = list(mesh.devices).index(shard.device)
            assert shard.index[0] == slice(c * per, (c + 1) * per)
            want = np.concatenate([
                np.asarray(catalogue.block(seed, catalogue.CATALOGUE, b, r,
                                           dist, br))
                for b in range(4 * c, 4 * c + 4)])
            np.testing.assert_array_equal(np.asarray(shard.data), want)
        def compiled(shard_rows):
            maker = catalogue._sharded_maker(mesh, shard_rows, r, br,
                                             catalogue._freeze(dist))
            return maker.lower(catalogue.seed_key(seed, 0)).compile()

        hlo = compiled(per).as_text()
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute", "reduce-scatter"):
            assert op not in hlo, op
        # each chip holds its share, and the making's scratch is the same
        # for 4 blocks a shard as for 64: a block's worth, not a shard's
        # (a few blocks on the CPU, 2 % of one on a v5e)
        small, large = (compiled(per).memory_analysis(),
                        compiled(16 * per).memory_analysis())
        assert small.output_size_in_bytes == per * r * 4
        assert large.output_size_in_bytes == 16 * per * r * 4
        assert small.temp_size_in_bytes == large.temp_size_in_bytes
        assert large.temp_size_in_bytes < 8 * br * r * 4
    """)


def test_a_sharded_catalogue_of_unequal_shards_is_refused():
    with pytest.raises(ValueError, match="multiple of chips x block_rows"):
        catalogue.rows(1, catalogue.CATALOGUE, 4 * 512 + 512, 16,
                       {"kind": "lowrank_spectrum"}, 512, chips=4)


# the one-chip catalogue of the parent commit, verbatim: the bits every
# one-chip cell's readings were taken on

def _parent_scale(dist: dict, rank: int) -> jax.Array:
    kind = dist["kind"]
    if kind == "lowrank_spectrum":
        return 1.0 / jnp.sqrt(1.0 + jnp.arange(rank, dtype=jnp.float32))
    if kind == "normal":
        return jnp.full((rank,), float(dist["std"]), jnp.float32)
    raise ValueError(f"unknown row distribution {kind!r}")


def _parent_block(key, b, block_rows: int, rank: int, dist: dict):
    rows = jax.random.normal(jax.random.fold_in(key, b),
                             (block_rows, rank), jnp.float32)
    return rows * _parent_scale(dist, rank)[None, :]


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _parent_all_rows(key, n_rows: int, rank: int, block_rows: int,
                     dist_items):
    dist = dict(dist_items)
    nb = -(-n_rows // block_rows)
    blocks = jax.vmap(lambda b: _parent_block(key, b, block_rows, rank,
                                              dist))(
        jnp.arange(nb, dtype=jnp.uint32))
    return blocks.reshape(nb * block_rows, rank)[:n_rows]


@pytest.mark.parametrize("config", ["retrieval_1m", "lmhead_ds67b"])
@pytest.mark.parametrize("stream", ["catalogue", "queries"])
def test_one_chip_rows_keep_the_parent_bits(config, stream):
    cfg = spec.load_config(config)
    dist = cfg[stream]
    sid = {"catalogue": catalogue.CATALOGUE,
           "queries": catalogue.QUERIES}[stream]
    seed, rank, br = 2**37 + 11, int(cfg["rank"]), int(cfg["block_rows"])
    n = br + 100               # a whole block and a cut one
    got = catalogue.rows(seed, sid, n, rank, dist, br)
    want = _parent_all_rows(catalogue.seed_key(seed, sid), n, rank, br,
                            tuple(sorted(dist.items())))
    assert got.sharding == want.sharding
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lognormal_norms_spread_the_row_norms_as_configured():
    base = {"kind": "lowrank_spectrum"}
    sigma, n = 0.8, 8192
    skewed = np.asarray(catalogue.rows(
        5, catalogue.CATALOGUE, n, 64,
        {"kind": "lognormal_norms", "sigma": sigma, "base": base}, 1024),
        np.float64)
    flat = np.asarray(catalogue.rows(5, catalogue.CATALOGUE, n, 64, base,
                                     1024), np.float64)
    # each row is its base row, scaled by exp(sigma * z_row)
    factor = np.linalg.norm(skewed, axis=1) / np.linalg.norm(flat, axis=1)
    np.testing.assert_allclose(skewed, flat * factor[:, None], rtol=1e-5)
    log_factor = np.log(factor)
    # z ~ N(0, 1) over 8,192 rows: the sample std lies within 3 % of 1
    assert abs(log_factor.std() / sigma - 1) < 0.03
    assert abs(log_factor.mean()) < 3 * sigma / np.sqrt(n)
    # the log-norms spread by the base's own spread and sigma together
    log_norm = np.log(np.linalg.norm(skewed, axis=1))
    base_sd = np.log(np.linalg.norm(flat, axis=1)).std()
    assert log_norm.std() == pytest.approx(np.hypot(sigma, base_sd),
                                           rel=0.05)
