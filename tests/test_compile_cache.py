"""Where the entry points keep JAX's persistent compilation cache."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_PROBE = """
    import os
    import jax, jax.numpy as jnp
    from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
    got = enable_compile_cache()
    print("DIR", got)
    print("CONFIG", jax.config.jax_compilation_cache_dir)
    print("DEFAULT", DEFAULT_DIR)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # compile one program, so that its entry is written
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0))
"""


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_PROBE)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def test_env_dir_holds_every_entry(tmp_path):
    """$JAX_COMPILATION_CACHE_DIR set: the code sets no other directory,
    and the compiled entry lands there."""
    out = _probe(tmp_path)
    assert out["DIR"] == str(tmp_path)
    assert out["CONFIG"] == str(tmp_path)
    assert any(tmp_path.iterdir())


@pytest.mark.parametrize("env_dir", [None, ""], ids=["unset", "empty"])
def test_default_dir_is_fixed_at_checkout_root(env_dir):
    """Unset (or empty): the fixed `.jax_cache/` at the checkout root."""
    out = _probe(env_dir)
    assert out["DEFAULT"] == str(REPO / ".jax_cache")
    assert out["DIR"] == out["CONFIG"] == out["DEFAULT"]
