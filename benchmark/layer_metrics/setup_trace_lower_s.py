"""Set-up: seconds of set-up in which the program traced its jitted
functions to jaxprs or lowered them to MLIR, and no backend step was open.
Paid in every process, warm cache or cold."""

from benchmark.layer_metrics import _setup_ledger


def read(record: dict):
    return _setup_ledger.read(record, "trace_lower_s")
