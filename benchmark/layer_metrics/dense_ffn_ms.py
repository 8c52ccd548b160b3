"""Dense FFN: device milliseconds a step in the leading dense layers' FFN
(scope ``dense_ffn``), forward, recomputed and backward."""

from benchmark.layer_metrics import _scope_ms


def read(record: dict):
    return _scope_ms.read(record, ("dense_ffn",))
