"""The persistent compilation cache keeps filling when the environment bounds
its size and the checkout's cache already holds an entry written without
a bound (no access-time file beside it): JAX's bounded cache then fails
every write, so every run would compile. `run.cache_settings` turns the
bound off. Each case runs in a fresh process, since the cache is set up
once per process."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PROBE = """
import os, sys
sys.path[:0] = [{root!r}]
d = {cache!r}
os.makedirs(d, exist_ok=True)
open(os.path.join(d, "unbounded-0-cache"), "wb").write(b"x")
import jax
if {fix}:
    from bench.run import cache_settings
    cache_settings()
else:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()
print(sum(f.endswith("-cache") for f in os.listdir(d)))
"""


@pytest.mark.parametrize("fix,written", [(False, 1), (True, 2)])
def test_bounded_cache_beside_unbounded_entry(tmp_path, fix, written):
    cache = str(tmp_path / "cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache,
               JAX_COMPILATION_CACHE_MAX_SIZE=str(10**9))
    out = subprocess.run(
        [sys.executable, "-c", PROBE.format(root=ROOT, cache=cache, fix=fix)],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    assert int(out.stdout.split()[-1]) == written
