"""Backend compiles inside the measured window (jax.monitoring). Every
shape is warmed up in set-up, so 0 is expected."""


def read(ctx):
    return ctx.get("window_compiles")
