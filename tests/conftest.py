"""Test harness configuration.

Reference parity: the DistributedQueryRunner pattern (SURVEY.md §4.3) —
multi-node testing without a cluster. TPU analogue: force 8 virtual CPU
devices so every sharding/collective test exercises a real 8-device mesh
on any machine (no TPU needed for correctness CI).

Must set env vars BEFORE jax initialises its backends.
"""

import os

# Force-set (not setdefault): the environment may name a real
# accelerator platform; correctness CI must run CPU-only. Set before
# jax is imported, the variable is enough.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
