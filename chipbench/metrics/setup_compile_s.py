"""Seconds of backend compilation during set-up (jax.monitoring); near 0
when every program came from the persistent cache."""


def read(ctx):
    return ctx.get("setup_compile_s")
