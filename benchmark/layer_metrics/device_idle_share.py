"""Device: share of the traced slice in which no operation ran on the chip
(``benchmark/trace_reduce.py``), in percent."""


def read(record: dict):
    if not record.get("trace"):
        return None
    return 100.0 * record["trace"]["idle_share"]
