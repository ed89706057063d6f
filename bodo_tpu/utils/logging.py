"""Verbose logging (analogue of bodo/user_logging.py levels 0-3).

Level 1: pushdown/fallback/IO notices; 2: plan dumps; 3: kernel trace.
"""

from __future__ import annotations

import sys
import warnings

from bodo_tpu.config import config


def log(level: int, msg: str) -> None:
    if config.verbose_level >= level:
        print(f"[bodo_tpu] {msg}", file=sys.stderr)


def warn_fallback(api: str, reason: str) -> None:
    """Emit the pandas-fallback warning (reference: check_args_fallback
    warning, bodo/pandas/utils.py:346)."""
    warnings.warn(
        f"{api}: falling back to pandas ({reason}); this materializes "
        f"the frame on the host", stacklevel=3)
