"""Set-up: seconds of set-up in backend steps that missed the persistent
compilation cache (XLA compiled).  About 0 in a warm run; the cold compile
in a cold one."""

from benchmark.layer_metrics import _setup_ledger


def read(record: dict):
    return _setup_ledger.read(record, "compile_s")
