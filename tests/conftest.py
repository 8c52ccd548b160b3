"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's multi-process-without-a-cluster strategy
(apex/transformer/testing/distributed_test_base.py:30 spawns world_size
processes on one host). On the JAX side one process with 8 virtual CPU
devices exercises the same mesh/collective code paths.

Hardware kernel tests (`pytest -m tpu tests/test_on_tpu_kernels.py`, on
a machine with a chip) set ``APEX_TPU_TEST_ON_TPU=1`` to keep the real
chip attached instead (the `tpu` marker is excluded by default —
pyproject addopts).

Must set env vars before jax is imported anywhere.
"""

import os

_ON_TPU = os.environ.get("APEX_TPU_TEST_ON_TPU") == "1"

if not _ON_TPU:
    # Force CPU before jax is imported: the unit tests never touch a
    # chip, whatever the environment's default platform is.
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
# Keep x64 off (TPU-realistic numerics).
os.environ.setdefault("JAX_ENABLE_X64", "0")


import pytest  # noqa: E402


@pytest.fixture
def flash_bwd(monkeypatch):
    """``flash_bwd("fused")`` / ``flash_bwd("split")`` run that flash
    backward at any size by moving ``_bwd_plan``'s key-length constant.
    A test seam: the program decides from the static shape alone and has
    no such option."""
    from apex_tpu.ops import flash_attention as fa

    def pin(plan):
        monkeypatch.setattr(fa, "_FUSED_BWD_MAX_SK",
                            {"fused": 1 << 30, "split": 0}[plan])

    return pin
