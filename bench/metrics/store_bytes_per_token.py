"""Bytes through the state store (put and get, as its traffic report counts
them) over the window, per token trained."""


def read(r):
    ctx = r.ctx
    if not ctx["tokens"]:
        return None
    return ctx["store_bytes"] / ctx["tokens"]
