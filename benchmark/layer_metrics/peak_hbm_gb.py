"""Device: ``memory_stats()["peak_bytes_in_use"]`` after the window, in GB
(1e9 bytes).  The allocator's reading for the whole process, set-up
included; not the compiler's ``memory_analysis()``."""


def read(record: dict):
    if not record.get("memory_peak_bytes"):
        return None
    return record["memory_peak_bytes"] / 1e9
