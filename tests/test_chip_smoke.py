"""``chip_smoke.py`` off the chip: it refuses a machine without a TPU, and
its phases (serving, mutation, compaction, the float64 reference check,
the sharded catalogue) run at a tiny size on the CPU backend, so a
change that breaks them fails here before it costs chip time."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _env(**extra):
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.path.join(REPO, "src"), **extra)


def test_chip_smoke_refuses_a_machine_without_tpu(tmp_path):
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
                       cwd=REPO)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_one_chip_phases_at_tiny_size():
    lines = []
    stats = chip_smoke.serve_one_chip(m=3000, r=16, per_method=12,
                                      max_batch=4, seed=1,
                                      log=lines.append)
    assert stats["n_compactions"] == 1
    assert stats["n_inserts"] == 64 and stats["n_updates"] == 8
    assert sum("exact=True" in ln for ln in lines) == 3 * 5
    assert lines[-1] == "engine traces after warmup: 0"


def test_dense_reference_rejects_a_bf16_precision_answer():
    """The reference tolerance is the float32 rounding bound: an answer
    scored with bf16 products fails it."""
    rng = np.random.default_rng(0)
    T = rng.standard_normal((500, 64)).astype(np.float32)
    ref = chip_smoke.DenseReference(T)
    u = rng.standard_normal(64).astype(np.float32)
    vals, ids = ref.topk(u[None], 10)
    exact = (T[ids[0]].astype(np.float64) @ u.astype(np.float64))
    assert ref.check(u, exact.astype(np.float32), ids[0], vals[0],
                     ids[0]) == 0
    import jax.numpy as jnp
    bf16 = np.asarray((jnp.asarray(T[ids[0]], jnp.bfloat16).astype(
        jnp.float32) @ jnp.asarray(u, jnp.bfloat16).astype(jnp.float32)))
    with pytest.raises(chip_smoke.SmokeFailure):
        ref.check(u, bf16, ids[0], vals[0], ids[0])


def test_chip_smoke_sharded_phase_on_four_cpu_devices():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import jax, chip_smoke
        assert len(jax.devices()) == 4, jax.devices()
        chip_smoke.serve_sharded(m=4001, r=16, n_queries=40)
        print("SHARDED_SMOKE_OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=560, cwd=REPO,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "SHARDED_SMOKE_OK" in r.stdout
    assert "real={0: 1001, 1: 1000, 2: 1000, 3: 1000}" in r.stdout
