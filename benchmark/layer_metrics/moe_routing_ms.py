"""Expert layer: device milliseconds a step in routing (scopes ``router``:
scores and selection; ``moe_dispatch``: sort, offsets, gather;
``moe_combine``: weigh and un-sort), forward, recomputed and backward."""

from benchmark.layer_metrics import _scope_ms


def read(record: dict):
    return _scope_ms.read(record, ("router", "moe_dispatch", "moe_combine"))
