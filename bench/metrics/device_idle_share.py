"""Share of the traced window in which a chip runs no operation, in %:
one minus the union of the chips' operation intervals over the window
(the ``bench.step`` host spans), averaged over chips."""


def read(ctx):
    t = ctx.get("trace") or {}
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
