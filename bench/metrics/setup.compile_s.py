"""Seconds XLA spent building executables during set-up: the sum of the
``/jax/core/compile/backend_compile_duration`` events up to the end of the
warm-up study (executables the persistent cache served are counted at the
time they took to load)."""


def read(ctx):
    return ctx.setup["compile_s"]
