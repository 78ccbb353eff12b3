"""queries_per_s: answers counted in the window over the window's length.

The window runs from the first request to the completion of the wave that
was running at ``--seconds`` (``harness._closed_window``)."""


def read(ctx):
    if not ctx.in_window or ctx.window_s <= 0:
        return None
    return len(ctx.in_window) / ctx.window_s
