"""Latent attention: device milliseconds a step in rope on the rotary
channels (scope ``mla_rope``: the heads' rotary queries and the one rotary
key, turned in pairs), forward, recomputed and backward."""

from benchmark.layer_metrics import _scope_ms


def read(record: dict):
    return _scope_ms.read(record, ("mla_rope",))
