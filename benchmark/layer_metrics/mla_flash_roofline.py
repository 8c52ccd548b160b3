"""Kernels: the latent-attention flash kernels' share of their roofline, in
percent.  The least time the chip could take for the step's attention (the
larger of its least operations over the bf16 peak and its least bytes over
the HBM peak, ``benchmark/flops_mla_moe.py`` ``flash_step_work``: the causal
triangle alone, the forward once, q/k 192 and v 128 wide, the rotary key
once a position) over the device time of the three kernels (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``) in the traced slice.  Whatever the
kernels pad, broadcast or recompute beyond that counts against them."""

from benchmark.layer_metrics import _device_op_ms


def read(record: dict):
    work, peaks = record.get("mla"), record.get("peaks")
    kernel_ms = _device_op_ms.read(
        record, lambda name: name == "flash_fwd"
        or name.startswith("flash_bwd"))
    if not work or not peaks or not kernel_ms:
        return None
    least_s = max(work["flash_flops_a_step"] / peaks["bf16_flops_per_s"],
                  work["flash_bytes_a_step"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (kernel_ms / 1e3)
