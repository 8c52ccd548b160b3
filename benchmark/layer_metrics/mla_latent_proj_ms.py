"""Latent attention: device milliseconds a step in the projections through
the latents (scopes ``mla_q_latent``: down, norm, up to the heads' two
parts; ``mla_kv_latent``: down, norm, up to keys and values; ``mla_out``),
forward, recomputed and backward."""

from benchmark.layer_metrics import _scope_ms


def read(record: dict):
    return _scope_ms.read(
        record, ("mla_q_latent", "mla_kv_latent", "mla_out"))
