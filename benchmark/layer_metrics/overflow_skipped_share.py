"""AMP: share of the window's steps whose update the loss scaler skipped
(the step's own ``overflow`` flag, fetched after the window), in percent."""


def read(record: dict):
    if not record.get("overflow"):
        return None
    return 100.0 * sum(record["overflow"]) / len(record["overflow"])
