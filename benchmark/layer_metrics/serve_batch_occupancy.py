"""Scheduler: live lanes a decode step, mean over the window's steps that
decoded, as a share of the engine's lanes (``max_slots``), in percent.  The
lanes of a step are the engine's count after it (``stats()["active"]``)
and the requests it completed."""

import numpy as np


def read(record: dict):
    serve = record.get("serve")
    if not serve:
        return None
    lanes = serve["decoded_lanes"][serve["decoded_lanes"] > 0]
    if not lanes.size:
        return None
    return 100.0 * float(np.mean(lanes)) / serve["max_slots"]
