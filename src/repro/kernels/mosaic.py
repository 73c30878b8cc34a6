"""The one switch between compiled and interpreted Pallas kernels.

Every kernel in this package is launched through :func:`pallas_call`. It
stages both forms of the call and lets jax keep one when the enclosing
program is lowered, from the platform it is lowered for: compiled by
Mosaic on ``tpu``, run by the Pallas interpreter on ``cpu`` (where the
tests run). No caller passes an ``interpret`` flag, so no configuration
can send the interpreter to a chip, and a compile for a described TPU
topology (tests/test_tpu_compile.py) compiles the real kernel.
"""

from __future__ import annotations

from jax import lax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` with ``interpret`` chosen by
    the lowering platform (see module docstring)."""
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return lax.platform_dependent(*args, cpu=interpreted, tpu=compiled)

    return call
