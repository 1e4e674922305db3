"""utils/compile_cache.configure: where the persistent cache lands.

Each case runs in a child interpreter on the CPU: configure() changes
process-global JAX config, and tests themselves never get a cache.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = ("from deepflow_tpu.utils import compile_cache; import jax; "
         "print(compile_cache.configure()); "
         "print(jax.config.jax_compilation_cache_dir)")


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_dir_is_the_env_dir_or_the_fixed_repo_dir(tmp_path,
                                                        from_env):
    want = str(tmp_path / "jc") if from_env else \
        os.path.join(REPO, ".jax_cache")
    used, configured = _probe(want if from_env else None)
    assert used == configured == want


def test_no_cache_at_import():
    """Importing the package (as every test does) sets no cache."""
    import jax

    import deepflow_tpu.utils.compile_cache  # noqa: F401
    assert jax.config.jax_compilation_cache_dir in (
        None, "", os.environ.get("JAX_COMPILATION_CACHE_DIR"))
