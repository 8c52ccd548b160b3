"""Device milliseconds a step under the program's scopes ``words``, from a
traced run of an entry that splits the trace by the cell's own scope words
(``record["scope_ms"]``, ``benchmark/entries/train_moe.py``).  ``None``
without a trace, and where the program has none of these scopes."""


def read(record: dict, words):
    found = [record.get("scope_ms", {}).get(w) for w in words]
    found = [ms for ms in found if ms is not None]
    return sum(found) if found else None
