"""Kernels: device milliseconds a decode step in the paged decode-attention
kernel: its own time (``trace_reduce``'s rule) in the traced slice, by the
names the cell file's ``decode_kernel.ops`` gives it, over the decode steps
that ran inside the slice (``entries/serve.py``).  ``None`` without a
trace, or where no operation of those names ran."""


def read(record: dict):
    kernel = record.get("decode_kernel")
    if not kernel or not kernel["own_s"] or not kernel["steps"]:
        return None
    return 1e3 * kernel["own_s"] / kernel["steps"]
