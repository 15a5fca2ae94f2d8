"""Process-0-gated structured logging.

The reference's observability is bare ``print`` gated on the main process
(``accelerator.print``, reference test_data_parallelism.py:165-166;
``if rank == 0``, test_model_parallelism.py:314-315). Here: ``get_logger``
returns an ordinary (ungated) ``logging`` logger; ``log0`` is the
process-0-gated emission helper that call sites should use for anything that
would otherwise print once per host.

Ungated lines carry ``p{process_index}`` so multi-host logs are attributable
to their host, and ``PDT_TPU_LOG_LEVEL`` (DEBUG/INFO/WARNING/... or a
number) sets the level without code changes. ``set_log_format("json")``
(the ``--log-format json`` CLI flag) switches every framework logger to
one-JSON-object-per-line records for machine scraping.
"""

from __future__ import annotations

import json
import logging
import os
import sys

_FORMATS = ("text", "json")
_TEXT_FMT = "[%(asctime)s %(levelname)s p%(pindex)s %(name)s] %(message)s"
_current_format = "text"
_configured: set[str] = set()  # logger names whose handlers we own


def _process_index() -> int:
    """This process's rank from the launcher contract (``JAX_PROCESS_ID``;
    ``comms.bootstrap.initialize`` exports it after a cluster auto-detect).
    Never ``jax.process_index()``: that initializes the default backend,
    and a log line from the jax-free fleet coordinator would then claim
    the chip its replicas need."""
    return int(os.environ.get("JAX_PROCESS_ID", "0"))


class _ProcessIndexFilter(logging.Filter):
    """Stamp the emitting host's process index on every record (resolved at
    emit time — the rendezvous may complete after the logger exists)."""

    def filter(self, record: logging.LogRecord) -> bool:
        record.pindex = _process_index()
        return True


class _JsonFormatter(logging.Formatter):
    def format(self, record: logging.LogRecord) -> str:
        return json.dumps(
            {
                "ts": record.created,
                "level": record.levelname,
                "logger": record.name,
                "process": getattr(record, "pindex", 0),
                "msg": record.getMessage(),
            }
        )


def _make_formatter() -> logging.Formatter:
    if _current_format == "json":
        return _JsonFormatter()
    return logging.Formatter(_TEXT_FMT)


def _resolve_level() -> int:
    raw = os.environ.get("PDT_TPU_LOG_LEVEL", "").strip()
    if not raw:
        return logging.INFO
    if raw.isdigit():
        return int(raw)
    level = logging.getLevelName(raw.upper())
    return level if isinstance(level, int) else logging.INFO


def get_logger(name: str = "pdt_tpu") -> logging.Logger:
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stdout)
        handler.addFilter(_ProcessIndexFilter())
        handler.setFormatter(_make_formatter())
        logger.addHandler(handler)
        logger.setLevel(_resolve_level())
        logger.propagate = False
        _configured.add(name)
    return logger


def set_log_format(fmt: str) -> None:
    """Switch already-configured and future framework loggers between
    human-readable text and JSON-lines records (the --log-format flag)."""
    global _current_format
    if fmt not in _FORMATS:
        raise ValueError(f"log format must be one of {_FORMATS}, got {fmt!r}")
    _current_format = fmt
    for name in _configured:
        for handler in logging.getLogger(name).handlers:
            handler.setFormatter(_make_formatter())


def log0(msg: str, *args, logger: logging.Logger | None = None) -> None:
    """Log on process 0 only (the reference's rank-0 print pattern)."""
    if _process_index() == 0:
        (logger or get_logger()).info(msg, *args)
