"""Test configuration: run everything on a virtual 8-device CPU mesh
(the TPU-world analog of the reference's loopback multi-process NCCL
tests — SURVEY.md §4).

Tests run CPU-only: the platform is pinned to 'cpu' BEFORE the first
backend initialization (chip runs go through chip_smoke.py).
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
jax.devices()  # init the CPU backend single-threaded, up front


def reset_dist_state():
    """Shared teardown for distributed tests: drop the global mesh and
    hybrid topology (use instead of per-file copies)."""
    from paddle_tpu.distributed.fleet.base.topology import _set_hcg
    from paddle_tpu.distributed.mesh import reset_mesh

    reset_mesh()
    _set_hcg(None)
