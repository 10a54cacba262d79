"""Device idle share of a training window: 1 - busy / window, in %, mean
over the cell's devices (profiler trace, ``bench/benchlib/trace.py``)."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
