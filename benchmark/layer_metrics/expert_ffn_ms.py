"""Expert layer: device milliseconds a step in the experts' FFN (scope
``expert_ffn``: the grouped products' kernels ``gmm_fwd``, ``gmm_dx`` and
``gmm_dw``, and the SwiGLU between them), forward, recomputed and backward."""

from benchmark.layer_metrics import _scope_ms


def read(record: dict):
    return _scope_ms.read(
        record, ("expert_ffn", "gmm_fwd", "gmm_dx", "gmm_dw"))
