"""Requests per dispatch cycle of the server's micro-batcher over the
window: the change in its ``requests`` counter over the change in
``cycles`` (``BPMFServer.stats()["batcher"]``)."""


def read(ctx):
    lay = ctx["layer"]
    if not lay.get("cycles"):
        return None
    return lay["requests"] / lay["cycles"]
