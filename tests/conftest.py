"""Test harness: force an 8-device virtual CPU mesh.

Tests run on the CPU whatever the machine holds: backend *initialization*
is lazy, so switching the platform to CPU here (before any jax op runs)
works even where JAX would default to a chip. Multi-chip shardings are
then validated on 8 virtual CPU devices, matching the driver's dryrun
contract.

The persistent compile cache is a directory of this process's own, made
empty here and removed at exit, so a test session never reads what an
earlier one compiled, and a program that several tests build alike (a
cell's stand-ins, one model's round in two files) compiles once per
process. JAX opens the cache once per process, and it is opened here
before any test runs: an entry point called in-process that places the
cache at ``<checkout>/.jax_cache`` (utils.use_compile_cache) changes the
option but not the open cache. Subprocesses get no cache from here.
"""

import atexit
import os
import shutil
import tempfile

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402
from jax._src import compilation_cache  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

_COMPILE_CACHE = tempfile.mkdtemp(prefix="jax-compile-cache-")
atexit.register(shutil.rmtree, _COMPILE_CACHE, ignore_errors=True)
jax.config.update("jax_enable_compilation_cache", True)
jax.config.update("jax_compilation_cache_dir", _COMPILE_CACHE)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
compilation_cache.reset_cache()
compilation_cache._initialize_cache()

assert jax.default_backend() == "cpu" and len(jax.devices()) >= 8, (
    "tests require the 8-device virtual CPU mesh; got "
    f"{jax.default_backend()} x{len(jax.devices())}"
)
