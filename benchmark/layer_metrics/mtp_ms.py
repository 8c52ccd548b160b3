"""Multi-token prediction: device milliseconds a step of everything under
the program's scope ``mtp`` (the merge of embedding and hidden state, the
module's block with its latent attention and its expert layer, its norm and
its head+CE), forward, recomputed and backward
(``record["scope_under_ms"]``, ``benchmark/entries/train_mla_moe.py``).
``None`` without a trace, and where the program has no such scope."""


def read(record: dict):
    return record.get("scope_under_ms", {}).get("mtp")
