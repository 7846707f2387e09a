"""Output tokens the engine emitted inside the window (timestamps taken in
the ``on_token`` callback), over the window's seconds."""


def read(run):
    n = sum(run.in_window(t) for r in run.requests for t in r.token_t)
    return n / run.window_s
