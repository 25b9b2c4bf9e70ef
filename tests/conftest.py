"""Test environment: an 8-device virtual CPU platform.

The tests run on the CPU; the chip is reached through ``chip_smoke.py``.
The env vars reach child processes (examples, CLI, DCN workers); the config
writes cover a jax imported before this file."""

import os

# XLA_FLAGS is read at backend-init time, so the env route works for it.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent compilation cache: the suite is dominated by 8-device shard_map
# compiles (and subprocess tests — examples, CLI, DCN workers — that re-jit
# the same programs in fresh interpreters). Env var rather than config-only
# so child processes inherit it.
_cache = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      ".jax_cache")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _cache)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir",
                  os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_compile_time_secs",
                  float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))
