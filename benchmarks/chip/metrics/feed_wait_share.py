"""feed_wait_share: the server phase's waits on its host feed over the
window, in percent: the program's ``server.feed_wait`` spans, one per
``next()`` of the streamed path's ``DevicePrefetcher`` (the step loop
waiting for a batch gathered from the host pool and put on the device).
Nothing to read on the resident path, or in a program without the
span."""


def read(ctx):
    if ctx.driver != "server":
        return None
    waits = [s.dur_wall for s in ctx.spans if s.name == "server.feed_wait"]
    if not waits:
        return None
    return 100.0 * sum(waits) / ctx.info["window_s"]
