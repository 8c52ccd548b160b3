"""Set-up: seconds of set-up in backend steps that loaded the executable
from the persistent compilation cache."""

from benchmark.layer_metrics import _setup_ledger


def read(record: dict):
    return _setup_ledger.read(record, "cache_load_s")
