"""Programs lowered inside the measured window. Must be 0."""
NAMES = ("window_compiles",)


def read(ctx):
    return float(ctx["window_compiles"])
