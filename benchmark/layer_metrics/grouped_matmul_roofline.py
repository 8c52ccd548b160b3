"""Kernels: the grouped expert products' share of their roofline, in
percent.  The least time the chip could take for the step's grouped
products (the larger of their operations over the bf16 peak and their bytes
over the HBM peak, ``benchmark/flops_moe.py``, from the rows the step's own
counters put on held experts) over the device time of the three kernels
(``gmm_fwd``, ``gmm_dx``, ``gmm_dw``) in the traced slice."""

from benchmark.layer_metrics import _scope_ms


def read(record: dict):
    work, peaks = record.get("moe"), record.get("peaks")
    kernel_ms = _scope_ms.read(record, ("gmm_fwd", "gmm_dx", "gmm_dw"))
    if not work or not peaks or not kernel_ms:
        return None
    least_s = max(work["grouped_flops_a_step"] / peaks["bf16_flops_per_s"],
                  work["grouped_bytes_a_step"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (kernel_ms / 1e3)
