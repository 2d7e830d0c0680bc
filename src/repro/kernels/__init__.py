"""Pallas kernels.  Every ``pallas_call`` resolves its mode through
``interpret_mode``: compiled for the TPU, interpreted on the CPU."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a ``pallas_call`` runs in the Pallas interpreter.

    ``None`` (every kernel entry point's default) means interpret exactly
    when the default backend is the CPU, which has no Pallas compiler.
    ``True`` on any other backend raises: an interpreted kernel on the chip
    would silently stand in for the compiled one.  ``False`` compiles for
    the TPU, which is also how a CPU process compiles a kernel for a
    described, unattached TPU.
    """
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError("Pallas interpret mode is for the CPU backend; "
                         f"the default backend is {jax.default_backend()!r}")
    return bool(interpret)
