"""Kernels: the paged decode-attention kernel's share of its roofline, in
percent.  The least time the chip could take for what the traced slice's
decode steps asked of it: the least bytes (``benchmark/flops_serve.py``
``decode_kernel_bytes``: every live lane's K and V over its context, its
query and its output, and the output projection's weight where the kernel
applies it) over the HBM peak, against the kernel's own device time there.
Its operations are a few per byte, far under the chip's 240: the bytes
bound it."""


def read(record: dict):
    kernel, peaks = record.get("decode_kernel"), record.get("peaks")
    if not kernel or not peaks or not kernel["own_s"]:
        return None
    least_s = kernel["least_bytes"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel["own_s"]
