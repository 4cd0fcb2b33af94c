"""Pallas TPU kernels for the paper's hot spots, each with a pure-jnp
oracle (``ref.py``) and jitted wrappers (``ops.py``).

Every kernel entry point resolves its ``interpret`` argument through
``interpret_mode``: one rule for the whole package, so no caller can
forget a flag and silently run the Pallas interpreter on the chip.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a ``pallas_call`` runs in interpret mode.

    ``None`` (every entry point's default) follows the backend: interpret
    mode on the CPU backend (the test suite), the compiled kernel
    everywhere else.  An explicit ``False`` compiles the kernel, which is
    how a kernel is lowered ahead of time for a described TPU from a
    CPU-only process.  Interpret mode is refused on a TPU backend."""
    backend = jax.default_backend()
    if interpret is None:
        return backend == "cpu"
    if interpret and backend == "tpu":
        raise ValueError("Pallas interpret mode requested on a TPU backend; "
                         "kernels run compiled on the chip")
    return bool(interpret)
