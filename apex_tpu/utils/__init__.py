from apex_tpu.utils.logging import get_logger, set_logging_level  # noqa: F401
