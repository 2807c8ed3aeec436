"""Test env: force JAX onto CPU with 8 virtual devices BEFORE any test runs.

Mirrors the reference's multi-node-without-a-cluster strategy (SURVEY.md §4:
in-CT slave nodes) — sharding/collective tests run on a virtual 8-device mesh.
The platform is forced through jax.config so the suite stays on the CPU even
when JAX_PLATFORMS is unset on a machine that holds a TPU.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
