"""Trainer, the whole step: this run's own tokens per second times the
operations a token requires (``benchmark/flops.py``) over the chips' bf16
peak (``benchmark/peaks.py``), in percent."""


def read(record: dict):
    if not record.get("peaks"):
        return None
    rate = record["end_to_end"]["train_tokens_per_s"]
    peak = record["peaks"]["bf16_flops_per_s"] * record["chips"]
    return 100.0 * rate * record["flops_per_token"] / peak
