"""Set-up: ``setup_s`` less the three other set-up metrics: imports,
backend start, making the weights, the first steps running and the check's
copies to the host."""

from benchmark.layer_metrics import _setup_ledger


def read(record: dict):
    return _setup_ledger.read(record, "outside_compile_s")
