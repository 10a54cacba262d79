"""The algorithm's FLOP per sweep (``bench/benchlib/work.py``) times the
sweeps of the traced window, over the device busy time the trace shows
(mean over chips) times the chips' bf16 peak, in %: the sweep program's
share of the peak while it runs. Idle time is ``idle_share.train``'s."""


def read(ctx):
    t, lay = ctx["trace"], ctx["layer"]
    if not t or not t["devices"] or t["busy_s"] <= 0 or not lay.get("sweeps"):
        return None
    rate = lay["sweep_flops"] * lay["sweeps"] / t["busy_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
