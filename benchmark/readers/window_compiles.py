"""jaxpr traces and backend compiles that ``jax.monitoring`` reported inside the
window. Warm-up is right when this is 0."""


def read(ctx):
    return ctx["window_compiles"]
