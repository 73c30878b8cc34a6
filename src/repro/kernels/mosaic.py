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

import jax
from jax import lax
from jax.experimental import pallas as pl
from jax.extend.core import jaxpr_as_fun


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` with ``interpret`` chosen by
    the lowering platform (see module docstring).

    The kernel is traced once: the interpreted branch is the compiled
    call's jaxpr with ``interpret`` set, sharing its kernel jaxpr, so a
    call costs one kernel trace and the jit caches keep one copy.
    """
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)

    def call(*args):
        closed, out = jax.make_jaxpr(compiled, return_shape=True)(*args)
        interpreted = closed.replace(jaxpr=closed.jaxpr.replace(eqns=[
            e.replace(params={**e.params, "interpret": True})
            if e.primitive.name == "pallas_call" else e
            for e in closed.jaxpr.eqns
        ]))
        tree = jax.tree.structure(out)

        def branch(c):
            return lambda *a: jax.tree.unflatten(tree, jaxpr_as_fun(c)(*a))

        return lax.platform_dependent(
            *args, cpu=branch(interpreted), tpu=branch(closed)
        )

    return call
