"""The persistent compilation cache is placed from outside the program:
``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed path in the
checkout. Each case runs in a CPU-pinned subprocess, so this worker's own
JAX configuration is never touched."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = ("from repro.launch.compile_cache import use_compile_cache\n"
         "import jax\n"
         "print(use_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)\n"
         "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n")


def _run(args, cache_dir=None, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    r = subprocess.run([sys.executable, *args], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=REPO)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout.split()


def test_cache_dir_defaults_to_the_fixed_checkout_path():
    fixed = os.path.join(REPO, ".jax_cache")
    assert _run(["-c", PROBE]) == [fixed, fixed, "0"]


def test_serve_entry_point_writes_where_the_environment_says(tmp_path):
    assert _run(["-c", PROBE], tmp_path) == [str(tmp_path)] * 2 + ["0"]
    _run(["-m", "repro.launch.serve", "--targets", "300", "--rank", "8",
          "-n", "4", "--batch", "4", "--engine", "naive"], tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())
