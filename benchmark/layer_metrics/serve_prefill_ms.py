"""Prefill: the engine's own milliseconds for a request's admission
(``Response.prefill_ms``: the prompt's flash prefill at its bucket, the K/V
scattered into the paged pool, the first token sampled and read back),
median over the served requests."""

import numpy as np


def read(record: dict):
    serve = record.get("serve")
    if not serve or not serve["prefill_ms"]:
        return None
    return float(np.median(serve["prefill_ms"]))
