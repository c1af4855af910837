"""Test harness: force an 8-device virtual CPU mesh.

Tests run on the CPU whatever the machine holds: backend *initialization*
is lazy, so switching the platform to CPU here (before any jax op runs)
works even where JAX would default to a chip. Multi-chip shardings are
then validated on 8 virtual CPU devices, matching the driver's dryrun
contract. The persistent compile cache is off: entry points called
in-process place it at ``<checkout>/.jax_cache`` (utils.use_compile_cache),
and a test session must not read what an earlier one compiled.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)
jax.config.update("jax_enable_compilation_cache", False)

assert jax.default_backend() == "cpu" and len(jax.devices()) >= 8, (
    "tests require the 8-device virtual CPU mesh; got "
    f"{jax.default_backend()} x{len(jax.devices())}"
)
