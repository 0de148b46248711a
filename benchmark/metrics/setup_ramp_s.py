"""Seconds of set-up that are the ramp: load offered before the window
opens so that it opens on a settled batch."""
NAMES = ("setup_ramp_s",)


def read(ctx):
    return ctx["setup"].get("ramp_s")
