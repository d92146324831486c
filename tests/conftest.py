"""Test env: run JAX on CPU with 8 virtual devices so the multi-chip sharding tier can
be tested without TPU hardware (SURVEY.md section 4). XLA_FLAGS must carry the virtual
device count before the CPU client is first created; the CPU platform is pinned through
jax.config so a TPU on the host is never used by the tests."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
