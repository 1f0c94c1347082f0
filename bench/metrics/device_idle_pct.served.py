"""Share of the traced window in which no operation ran on the device,
in the served cell: what the serving driver and the arrivals leave idle."""


def read(ctx):
    s = ctx["summary"]
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
