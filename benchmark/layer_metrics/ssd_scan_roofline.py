"""Kernels: the state-space scan's share of its roofline, in percent.  The
least time the chip could take for the step's scans (the larger of their
operations over the bf16 peak and their least bytes over the HBM peak,
``benchmark/flops_nemotron_h.py``: the forward counted as often as it runs,
the backward as two passes) over the device time under the scope
``ssd_scan`` in the traced slice."""

from benchmark.layer_metrics import _scope_ms


def read(record: dict):
    work, peaks = record.get("ssm"), record.get("peaks")
    scan_ms = _scope_ms.read(record, ("ssd_scan",))
    if not work or not peaks or not scan_ms:
        return None
    least_s = max(work["scan_flops_a_step"] / peaks["bf16_flops_per_s"],
                  work["scan_bytes_a_step"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (scan_ms / 1e3)
