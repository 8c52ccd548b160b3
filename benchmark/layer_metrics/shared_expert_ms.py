"""Expert layer: device milliseconds a step in the shared expert (scope
``shared_expert``: two dense products and ``relu^2`` over every token),
forward, recomputed and backward."""

from benchmark.layer_metrics import _scope_ms


def read(record: dict):
    return _scope_ms.read(record, ("shared_expert",))
