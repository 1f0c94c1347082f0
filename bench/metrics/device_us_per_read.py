"""Device-busy microseconds per read mapped in the traced window."""


def read(ctx):
    if not ctx["reads"]:
        return None
    return ctx["summary"]["busy_s"] * 1e6 / ctx["reads"]
