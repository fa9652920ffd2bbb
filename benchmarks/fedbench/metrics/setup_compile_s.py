"""Seconds of backend compilation during set-up: the sum of JAX's
``/jax/core/compile/backend_compile_duration`` events (a persistent-cache
hit reports its retrieval time)."""


def read(ctx):
    return ctx["setup_compile_s"]
