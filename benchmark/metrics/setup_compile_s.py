"""Seconds of set-up spent tracing, lowering and compiling (or reading
the compile cache), from ``jax.monitoring``."""
NAMES = ("setup_compile_s",)


def read(ctx):
    return ctx["setup"].get("compile_s")
