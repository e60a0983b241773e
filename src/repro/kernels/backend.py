"""The one place that decides how Pallas kernels execute.

Every ``pl.pallas_call`` in :mod:`repro.kernels` takes a required
``interpret`` flag, and every call site passes :func:`pallas_interpret`.
On a TPU it is False, so a kernel there always goes through Mosaic, the
TPU kernel compiler; on any other backend (the CPU test target) the
kernels run in the Pallas interpreter.
"""
from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """True off-TPU: run Pallas kernels in interpret mode."""
    return jax.default_backend() != "tpu"
