"""Mamba-2 mixer: device milliseconds a step in the chunked state-space
scan alone (scope ``ssd_scan``: the time step's softplus, the decays, the
four products and the carry across chunks), forward, recomputed and
backward."""

from benchmark.layer_metrics import _scope_ms


def read(record: dict):
    return _scope_ms.read(record, ("ssd_scan",))
