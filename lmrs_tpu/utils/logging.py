"""Logging setup: one root config instead of the reference's per-module
copy-pasted ``basicConfig`` blocks (main.py:32-40, llm_executor.py:22-26, …).

Repeated ``setup_logging`` calls are honored: the managed handler's level,
stream, and format are UPDATED in place (the original first-call-wins
behavior silently ignored a later ``--quiet`` or a bench redirecting logs
to stderr after a library import had already configured stdout).  Handlers
installed by embedding applications are left untouched.

``LMRS_LOG_JSON=1`` switches the managed handler to one-JSON-object-per-
line output (ts/level/logger/msg) for log scraping; the env var is re-read
on every ``setup_logging`` call so tests and long-lived processes can
toggle it.
"""

from __future__ import annotations

import json
import logging
import sys

from lmrs_tpu.utils.env import env_bool

_FORMAT = "%(asctime)s - %(name)s - %(levelname)s - %(message)s"


class JsonFormatter(logging.Formatter):
    """One JSON object per line — machine-scrapable structured logs."""

    def format(self, record: logging.LogRecord) -> str:
        entry = {
            "ts": round(record.created, 3),
            "level": record.levelname,
            "logger": record.name,
            "msg": record.getMessage(),
        }
        if record.exc_info:
            entry["exc"] = self.formatException(record.exc_info)
        return json.dumps(entry, ensure_ascii=False)


def _managed_handler(root: logging.Logger) -> logging.StreamHandler | None:
    for h in root.handlers:
        if getattr(h, "_lmrs_managed", False):
            return h
    return None


def setup_logging(quiet: bool = False, level: int | None = None,
                  stream=None) -> None:
    """Configure the ``lmrs`` logger tree.  quiet → WARNING (main.py
    --quiet).  ``stream`` defaults to stdout (the reference logs to
    stdout, main.py:32-40); artifact-emitting callers whose stdout is a
    machine-read contract (one JSON line) pass stderr.
    Safe to call repeatedly — later calls update level/stream/format."""
    root = logging.getLogger("lmrs")
    formatter: logging.Formatter = (
        JsonFormatter() if env_bool("LMRS_LOG_JSON", False)
        else logging.Formatter(_FORMAT))
    handler = _managed_handler(root)
    if handler is None:
        # legacy compat: a pre-existing FOREIGN handler (an embedding app's)
        # is respected — we only manage handlers we created
        if not root.handlers:
            handler = logging.StreamHandler(stream if stream is not None
                                            else sys.stdout)
            handler._lmrs_managed = True
            root.addHandler(handler)
    elif stream is not None:
        handler.setStream(stream)
    if handler is not None:
        handler.setFormatter(formatter)
    root.setLevel(level if level is not None
                  else (logging.WARNING if quiet else logging.INFO))
