"""Where a Pallas kernel runs: compiled by Mosaic on a TPU, interpreted
everywhere else (the CPU test suite runs the same kernel bodies)."""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """The one decision of whether ``pallas_call`` interprets.

    ``None`` follows the backend: ``False`` on a TPU, ``True`` off it.
    An explicit bool is honoured — compile tests pass ``False`` to lower a
    kernel for a described TPU from a CPU-only process."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
