"""Host loop: milliseconds the user's step call takes to return (host
clock, not fenced), median over the window's steps.  Near the step time
means the host cannot run ahead of the device."""

import statistics


def read(record: dict):
    if not record.get("dispatch_s"):
        return None
    return 1e3 * statistics.median(record["dispatch_s"])
