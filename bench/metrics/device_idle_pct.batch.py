"""Share of the traced window in which no operation ran on the device,
in the closed-loop cells: what the streaming driver leaves idle."""


def read(ctx):
    s = ctx["summary"]
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
