"""Compile the serving path for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, so each test here lowers and
compiles an executor at deployment width — M = 2**20 catalogue rows,
R = 64, k = 10, B in {1, 64} — against a described ``v5e:2x2`` topology
and checks the compiled program's memory analysis. What the chip's
compiler refuses (an unaligned block, a primitive Mosaic cannot lower)
fails here, at no chip time; nothing runs, so nothing here is a result
or a timing.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and the
test workers all import every test file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.engines import _ARG_EXECUTORS, EngineContext, get_engine
from repro.kernels.topk_mips import (NEG_INF, _merge_block,
                                     topk_mips_pallas_batched_prefetch)

M_BUCKET = 1 << 20
R = 64
K = 10
#: builds the args pytrees whose shapes are scaled up to M_BUCKET; every
#: leaf dimension equal to it is a catalogue dimension (R = 64 and the
#: 2048-row list prefix never are)
SMALL_M = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without the chip: keep it off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def small_ctx():
    rng = np.random.default_rng(0)
    T = rng.standard_normal((SMALL_M, R)).astype(np.float32)
    return EngineContext(T, block_size=256, prefix_depth=2048)


@pytest.fixture
def tpu_backend(monkeypatch):
    """Code that asks ``jax.default_backend()`` at trace time (the merge
    network in ``core/driver.py``) takes its TPU branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _deployment_shapes(tree, sharding):
    def sds(x):
        shape = tuple(M_BUCKET if d == SMALL_M else d for d in x.shape)
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)
    return jax.tree_util.tree_map(sds, tree)


def _check_memory(compiled, arg_floor: int):
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= arg_floor, mem
    # one v5e chip holds 16 GB of HBM
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16e9, mem


@pytest.mark.parametrize("batch", [1, 64])
@pytest.mark.parametrize("engine", ["naive", "norm", "bta", "ta"])
def test_engine_executor_compiles_for_one_v5e(engine, batch, one_chip,
                                              small_ctx, tpu_backend):
    eng = get_engine(engine)
    args = _deployment_shapes(small_ctx.engine_args(eng), one_chip)
    acfg = eng.arg_config(small_ctx) if eng.arg_config is not None else ()
    # list engines: the mixed sign bucket, what dense embedding queries hit
    bcfg = (0, False) if eng.batch_config is not None else ()
    U = jax.ShapeDtypeStruct((batch, R), jnp.float32, sharding=one_chip)
    compiled = _ARG_EXECUTORS[engine].lower(
        args, U, k=K, cfg=(acfg, bcfg, None)).compile()
    _check_memory(compiled, M_BUCKET * R * 4)


def _selection_sizes(hlo: str) -> list:
    """Element count of the operand of every ``TopK`` custom call and
    every ``sort`` in a compiled module's text: XLA lowers ``top_k`` to
    either, and may itself split one row into many (at B = 1 a direct
    ``top_k`` over 2**20 lanes becomes a sort of ``[128, 8192]``)."""
    shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", hlo))
    sizes = []
    for line in hlo.splitlines():
        op = re.search(r"= .*?\b(?:custom-call|sort)\(%([\w.\-]+)", line)
        if op and ('custom_call_target="TopK"' in line or " sort(" in line):
            sizes.append(int(np.prod([int(d) for d in
                                      shapes[op.group(1)].split(",")])))
    return sizes


@pytest.mark.parametrize("batch", [1, 64])
def test_naive_selection_never_spans_the_catalogue(batch, one_chip,
                                                   tpu_backend):
    """At the retrieval cell's width (2**20 rows x 256, k = 200) the
    naive executor selects in two stages: no ``TopK`` or ``sort`` takes
    as many scores as the whole catalogue per query, and the program
    fits one chip. Batched, the candidates' ``TopK`` stays a custom call
    of the entry computation, so a profile names it (DESIGN.md §4); at
    B = 1 the compiler lowers it to sorts."""
    r, k = 256, 200
    args = {"targets": jax.ShapeDtypeStruct((M_BUCKET, r), jnp.float32,
                                            sharding=one_chip),
            "m_real": jax.ShapeDtypeStruct((), jnp.int32,
                                           sharding=one_chip)}
    U = jax.ShapeDtypeStruct((batch, r), jnp.float32, sharding=one_chip)
    compiled = _ARG_EXECUTORS["naive"].lower(
        args, U, k=k, cfg=((), (), None)).compile()
    hlo = compiled.as_text()
    sizes = _selection_sizes(hlo)
    assert sizes and max(sizes) < batch * M_BUCKET, sizes
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    bare = entry.count('custom_call_target="TopK"')
    assert bare == (batch > 1), entry
    _check_memory(compiled, M_BUCKET * r * 4)


def test_norm_sharded_compiles_for_four_v5e(topo, tpu_backend):
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    rows = NamedSharding(mesh, P("data"))
    args = {
        "targets_sharded": jax.ShapeDtypeStruct(
            (M_BUCKET, R), jnp.float32,
            sharding=NamedSharding(mesh, P("data", None))),
        "norms_sharded": jax.ShapeDtypeStruct((M_BUCKET,), jnp.float32,
                                              sharding=rows),
        "ids_sharded": jax.ShapeDtypeStruct((M_BUCKET,), jnp.int32,
                                            sharding=rows),
    }
    U = jax.ShapeDtypeStruct((64, R), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    compiled = _ARG_EXECUTORS["norm_sharded"].lower(
        args, U, k=K, cfg=((256, -1, mesh), (), None)).compile()
    # the catalogue is split: each device holds a quarter of it
    _check_memory(compiled, M_BUCKET * R * 4 // 4)
    assert compiled.memory_analysis().argument_size_in_bytes \
        < M_BUCKET * R * 4 // 2


def test_pallas_kernel_is_refused_for_one_v5e(one_chip):
    """The ``pallas`` engine refuses a TPU backend because of these two
    refusals (``PALLAS_TPU_REFUSAL``): lift that guard when this fails."""
    tiles, bm, batch = 8, 256, 8
    steps = M_BUCKET // (bm * tiles)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(functools.partial(topk_mips_pallas_batched_prefetch, k=K,
                                   block_m=bm, tiles_per_step=tiles,
                                   interpret=False))
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        fn.lower(s((M_BUCKET, R), jnp.float32),
                 s((batch, steps, tiles), jnp.float32),
                 s((batch, steps), jnp.int32), s((batch, steps), jnp.int32),
                 s((batch, R), jnp.float32)).compile()

    # past the block checks, the in-kernel merge: Mosaic has no top_k
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    k, block = 128, 1024

    def kernel(s_ref, v_ref, i_ref, sv, si):
        sv[...] = jnp.full_like(sv, NEG_INF)
        si[...] = jnp.full_like(si, -1)
        _merge_block(s_ref[...], 0, sv, si, k=k, block_m=block,
                     num_real=block)
        v_ref[...] = sv[...]
        i_ref[...] = si[...]

    merge = jax.jit(lambda x: pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((k,), jnp.float32),
                   jax.ShapeDtypeStruct((k,), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((k,), jnp.float32),
                        pltpu.VMEM((k,), jnp.int32)])(x))
    with pytest.raises(NotImplementedError, match="top_k"):
        merge.lower(s((block,), jnp.float32)).compile()
