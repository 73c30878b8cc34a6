"""setup_s: process start to the first timed unit, in seconds: imports,
the context, placing it on the device, and one whole unit of the cell's
work, which traces and compiles (or loads from the cache) every program."""


def read(run):
    return run.setup_s
