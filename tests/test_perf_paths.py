"""Tests for the §Perf optimized code paths (EXPERIMENTS.md):
expert-parallel MoE dispatch and the grouped flash-decoding attention."""

import subprocess
import sys
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=560):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=REPO)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_moe_ep_matches_dense_dispatch():
    """EP dispatch (Perf-A) must be numerically identical to the pjit
    global dispatch when capacity is ample, including under sharding."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.models.moe import init_moe, moe_ffn, moe_ffn_ep
        from repro.models.common import MeshRules
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        for seed in range(3):
            params = init_moe(jax.random.PRNGKey(seed), 32, 48, 8)
            h = jax.random.normal(jax.random.PRNGKey(seed + 10), (4, 8, 32))
            ref, _ = moe_ffn(params, h.reshape(32, 32), top_k=2,
                             capacity_factor=8.0)
            with jax.set_mesh(mesh):
                out, aux = moe_ffn_ep(params, h, top_k=2,
                                      capacity_factor=8.0, rules=MeshRules())
            err = float(jnp.max(jnp.abs(out.reshape(32, 32) - ref)))
            assert err < 1e-4, (seed, err)
            assert float(aux["drop_rate"]) < 1e-6
        print("EP_PARITY_OK")
    """)
    assert "EP_PARITY_OK" in out


def test_moe_ep_capacity_dropping_is_bounded():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.models.moe import init_moe, moe_ffn_ep
        from repro.models.common import MeshRules
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        params = init_moe(jax.random.PRNGKey(0), 32, 48, 8)
        h = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32))
        with jax.set_mesh(mesh):
            out, aux = moe_ffn_ep(params, h, top_k=2, capacity_factor=1.0,
                                  rules=MeshRules())
        assert bool(jnp.all(jnp.isfinite(out)))
        d = float(aux["drop_rate"])
        assert 0.0 <= d < 0.6, d
        print("EP_DROP_OK", d)
    """)
    assert "EP_DROP_OK" in out


@pytest.mark.parametrize("n_heads,n_kv", [(8, 8), (8, 2), (4, 1)])
def test_grouped_decode_attention_matches_dense(n_heads, n_kv):
    """Perf-B grouped decode == reference softmax attention (incl. MQA)."""
    from repro.models.attention import decode_attention
    rng = np.random.default_rng(n_heads * 10 + n_kv)
    B, S, D = 2, 64, 16
    q = jnp.asarray(rng.standard_normal((B, 1, n_heads, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, n_kv, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, n_kv, D)).astype(np.float32))
    clen = jnp.asarray([40, 64], jnp.int32)
    out = decode_attention(q, k, v, cache_len=clen)
    # dense reference
    kk = jnp.repeat(k, n_heads // n_kv, axis=2)
    vv = jnp.repeat(v, n_heads // n_kv, axis=2)
    s = jnp.einsum("bqhd,bshd->bhqs", q / np.sqrt(D), kk)
    mask = jnp.arange(S)[None, None, None, :] < clen[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhqs,bshd->bqhd", p, vv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_kv_cache_specs_folds_idle_data_axis():
    """Perf-B iter 3: batch=1 -> sequence sharded over data AND model."""
    out = _run("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.models.common import MeshRules
        from repro.models.transformer import TransformerConfig, kv_cache_specs
        cfg = TransformerConfig(name="t", n_layers=2, d_model=32, n_heads=4,
                                n_kv_heads=2, head_dim=8, d_ff=64,
                                vocab_size=128)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        with jax.set_mesh(mesh):
            sp1 = kv_cache_specs(cfg, MeshRules(), batch=1, seq_len=64)["k"]
            sp8 = kv_cache_specs(cfg, MeshRules(), batch=8, seq_len=64)["k"]
        assert tuple(sp1[2]) == ("data", "model"), sp1   # CP over both axes
        assert sp8[1] in ("data", ("data",)) and sp8[2] == "model", sp8
        print("CP_SPEC_OK")
    """)
    assert "CP_SPEC_OK" in out
