"""Shared benchmark plumbing."""

from __future__ import annotations

import jax
import numpy as np


def fence(x) -> None:
    """Wait for the program producing ``x`` (dispatch is asynchronous; a
    timing that does not end here measures the enqueue)."""
    if x is not None and not isinstance(x, np.ndarray):
        jax.block_until_ready(x)
