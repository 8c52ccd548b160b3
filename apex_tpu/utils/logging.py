"""Rank-annotated logging.

TPU-native analog of the reference's library-root logger with
``RankInfoFormatter`` (reference apex/__init__.py:27-39) and the transformer
log utilities (reference apex/transformer/log_util.py). Rank info comes from
``jax.process_index`` instead of torch.distributed, and — when a mesh-based
model-parallel state is initialized — from
``apex_tpu.transformer.parallel_state.get_rank_info``.
"""

from __future__ import annotations

import logging
import sys

_LOGGER_NAME = "apex_tpu"


class RankInfoFormatter(logging.Formatter):
    """Prepends (host rank / mp rank info) to every record when available."""

    def format(self, record):
        rank_info = ""
        try:
            import jax

            # Cheap: process_index does not touch devices.
            rank_info = f"[host {jax.process_index()}/{jax.process_count()}]"
        except Exception:
            pass
        try:
            from apex_tpu.transformer import parallel_state

            if parallel_state.model_parallel_is_initialized():
                rank_info += str(parallel_state.get_rank_info())
        except Exception:
            pass
        record.rank_info = rank_info
        return super().format(record)


def _build_root_logger() -> logging.Logger:
    logger = logging.getLogger(_LOGGER_NAME)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(
            RankInfoFormatter(
                "%(asctime)s %(levelname)s %(rank_info)s %(name)s: %(message)s"
            )
        )
        logger.addHandler(handler)
        logger.setLevel(logging.WARNING)
        logger.propagate = False
    return logger


_ROOT = _build_root_logger()


def get_logger(name: str | None = None) -> logging.Logger:
    if name is None:
        return _ROOT
    return _ROOT.getChild(name)


def set_logging_level(level) -> None:
    """reference apex/transformer/log_util.py:set_logging_level analog."""
    _ROOT.setLevel(level)


def print_rank_0(message: str) -> None:
    """Print only on process 0 (reference pipeline_parallel/utils.py:159).

    Guarded the way ``RankInfoFormatter.format`` already is: with no
    reachable JAX backend (``jax.process_index`` raising mid-init) this
    degrades to printing instead of raising from inside a log call.
    """
    try:
        import jax

        rank = jax.process_index()
    except Exception:
        rank = 0
    if rank == 0:
        print(message, flush=True)
