"""Short convolution: device milliseconds a step in the conv layers'
operator (scope ``short_conv`` with ``conv_in``, ``conv_gate`` and
``conv_out``), forward, recomputed and backward."""

from benchmark.layer_metrics import _scope_ms


def read(record: dict):
    return _scope_ms.read(
        record, ("short_conv", "conv_in", "conv_gate", "conv_out"))
