"""idle_share.consolidate: the share of the traced window in which no operation
ran on the device, in percent: 1 - (union of the device's op intervals)
/ (window), from the profiler trace (``harness/trace.py``), averaged
over the chips used."""


def read(ctx):
    if ctx.driver != "consolidate" or ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
