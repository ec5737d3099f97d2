"""Test config: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's no-cluster strategy (testing/dist_common.py spawns N
local processes); on TPU/JAX the idiomatic substitute is
``xla_force_host_platform_device_count`` + ``shard_map`` in a single process.
Pallas kernels run in interpreter mode on CPU. The platform is pinned with
jax.config as well as by the tier-1 command's JAX_PLATFORMS=cpu, so a bare
``pytest`` on a host that has an accelerator still runs the CPU suite.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["MAGI_ATTENTION_PALLAS_INTERPRET"] = "1"
# run the whole suite with the expensive plan invariants on (ref
# MAGI_ATTENTION_SANITY_CHECK, env/general.py:75-84)
os.environ.setdefault("MAGI_ATTENTION_SANITY_CHECK", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
