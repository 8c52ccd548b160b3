"""Kernels: device milliseconds a step in the flash-attention backward
kernels (the custom calls under the program's scopes ``flash_bwd``, fused, or
``flash_bwd_dq`` and ``flash_bwd_dkv``, split)."""

from benchmark.layer_metrics import _device_op_ms


def read(record: dict):
    return _device_op_ms.read(
        record, lambda name: name.startswith("flash_bwd"))
