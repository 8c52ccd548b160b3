"""Engine step, the whole of it: the useful operations of the window's
steps (``benchmark/flops_serve.py``: each decode step's live lanes through
every product and attention over the K/V they hold, and each prompt
admitted inside the window through its prefill) over the window's
seconds, as a share of the chip's bf16 peak, in percent.  It bounds what
any kernel's gain can bring to ``serve_tokens_per_s``."""


def read(record: dict):
    serve, peaks = record.get("serve"), record.get("peaks")
    if not serve or not peaks or not serve["flops"]:
        return None
    return (100.0 * serve["flops"] / record["window_s"]
            / peaks["bf16_flops_per_s"])
