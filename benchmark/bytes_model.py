"""Bytes that do not depend on the model's architecture: the width of a
type and what a copy moves. A family's own bytes (its weights, its cache, a
fused step of it) are in ``bytes_models/<name>.py``, which the family's
adapter names. The yardstick of the roofline shares: a later PR cannot
change what a kernel is held to.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def page_copy_bytes(nbytes: int) -> int:
    """A copy reads its bytes once and writes them once."""
    return 2 * nbytes
