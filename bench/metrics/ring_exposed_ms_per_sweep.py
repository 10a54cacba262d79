"""Collective time of the traced window during which no other op runs on
the device, mean over devices, per sweep, in ms."""


def read(ctx):
    t, lay = ctx["trace"], ctx["layer"]
    if not t or t["devices"] < 2 or not lay.get("sweeps") or t["collective_s"] <= 0:
        return None
    return 1e3 * t["exposed_collective_s"] / lay["sweeps"]
