"""Share of the traced window in which no operation runs on the device."""


def read(ctx):
    r = ctx.get("trace")
    return None if r is None else 100.0 * r.idle_share
