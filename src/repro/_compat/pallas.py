"""Where Pallas kernels run: compiled by Mosaic on a TPU, interpreted
elsewhere."""

from typing import Optional

import jax


def default_interpret() -> bool:
    """Whether Pallas kernels should run in interpret mode by default.

    Compiled Pallas requires a real TPU backend; everywhere else (CPU CI,
    GPU hosts) the kernels must fall back to interpret mode.  All kernel
    entry points take ``interpret=None`` and resolve it here so the choice
    lives in exactly one place.
    """
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` → backend auto-detect; explicit booleans pass through."""
    return default_interpret() if interpret is None else bool(interpret)
