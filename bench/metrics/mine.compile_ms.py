"""mine.compile_ms: JAX's own /jax/core/compile/* seconds (tracing,
lowering, compiling or loading from the cache) per mine in the window, in
ms.  Each mine builds a fresh engine, so its steps are traced again."""

from harness.record import per_unit


def read(run):
    v = per_unit(run, "compile_s")
    return None if v is None else v * 1e3
