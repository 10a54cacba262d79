"""Device time of the tensor model's time chain per sweep, in ms: every op
whose scope path holds ``time_chain`` (the draws under ``posterior_draw``
inside it too), union over the window, mean over devices
(``kinds/train_bptf.py`` splits the trace)."""


def read(ctx):
    lay = ctx["layer"]
    if lay.get("time_chain_s") is None or not lay.get("sweeps"):
        return None
    return 1e3 * lay["time_chain_s"] / lay["sweeps"]
