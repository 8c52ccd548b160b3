"""Kernels: device milliseconds a step in the flash-attention forward kernel
(the custom call under the program's scope ``flash_fwd``; with remat it runs
twice a layer)."""

from benchmark.layer_metrics import _device_op_ms


def read(record: dict):
    return _device_op_ms.read(record, lambda name: name == "flash_fwd")
