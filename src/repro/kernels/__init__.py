"""Pallas kernels for the compute hot-spots the paper optimizes: the
pipeline computation engine (``conv2d``) and the generic MAC array
(``matmul``), plus the LM kernels the runtime's configurations use.

Each ``<name>/ops.py`` wrapper runs its kernel compiled on a TPU and in
Pallas interpret mode on the CPU (tests), chosen by :func:`interpret_mode`.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Pallas interpret mode for the default backend: interpreted on
    ``cpu``, compiled on ``tpu``; any other backend has no lowering for
    these kernels and is an error, never a silent interpreter run."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas TPU lowering on backend {backend!r}; "
                       f"the kernels run on 'tpu' (or interpreted on 'cpu')")
