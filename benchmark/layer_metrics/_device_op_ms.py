"""Device milliseconds a step of the operations whose name ``match``es, from
a traced run's ten operations with most own time (``record["trace"]``,
``benchmark/trace_reduce.py``): their share of the traced slice times the
timed window's own step time.  ``None`` without a trace, and where none of
the ten has such a name (a program that does not name its kernels apart)."""


def read(record: dict, match):
    trace = record.get("trace")
    if not trace:
        return None
    own_s = [s for name, s in trace["device_ops"] if match(name)]
    if not own_s:
        return None
    step_ms = 1e3 * record["window_s"] / record["steps"]
    return sum(own_s) / trace["window_s"] * step_ms
