"""The tensor model's Gram at its roofline: the least time of the traced
window's sweeps (``benchlib/gram3.py``: three sides, ``8K + 12`` bytes and
``2K^2 + 3K`` FLOP per rating and side) over the device time under
``bpmf_gram`` in the window, in % (``kinds/train_bptf.py`` splits the
trace)."""


def read(ctx):
    from benchlib import gram3

    lay = ctx["layer"]
    if not lay.get("gram_s") or not lay.get("sweeps"):
        return None
    least_s, _ = gram3.roofline_s(lay["num_train"], lay["K"], ctx["peaks"])
    return 100.0 * least_s * lay["sweeps"] / lay["gram_s"]
