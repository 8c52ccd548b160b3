"""Latent attention: device milliseconds a step of everything under the
program's scope ``mla_attention`` (the latent projections and norms, rope,
the flash kernels and what XLA puts around them, the output projection), in
the stack's blocks and the MTP module's, forward, recomputed and backward
(``record["scope_under_ms"]``, ``benchmark/entries/train_mla_moe.py``).
``None`` without a trace, and where the program has no such scope."""


def read(record: dict):
    return record.get("scope_under_ms", {}).get("mla_attention")
