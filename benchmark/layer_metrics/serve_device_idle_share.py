"""Device, serving: share of the traced slice of the window (its middle
arrivals, ``entries/serve.py``) in which no operation ran on the chip, in
percent (``benchmark/trace_reduce.py``).  The host loop drives every step
and waits on each step's tokens, so this is the chip's wait on the host."""

from benchmark.layer_metrics import device_idle_share


def read(record: dict):
    if "serve" not in record:
        return None
    return device_idle_share.read(record)
