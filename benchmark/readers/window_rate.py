"""Output tokens emitted inside the window, of all requests (those the window's
end cut included), over the window's length: the whole cell, not per chip."""


def read(ctx):
    s = ctx["summary"]
    return s["tokens_in_window"] / s["window_s"]
