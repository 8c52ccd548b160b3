"""Mamba-2 mixer: device milliseconds a step in the state-space layers'
mixer (scope ``mamba_mixer`` with ``ssm_in``, ``ssm_conv``, ``ssd_scan``,
``ssm_gate_norm`` and ``ssm_out``), forward, recomputed and backward."""

from benchmark.layer_metrics import _scope_ms


def read(record: dict):
    return _scope_ms.read(
        record, ("mamba_mixer", "ssm_in", "ssm_conv", "ssd_scan",
                 "ssm_gate_norm", "ssm_out"))
