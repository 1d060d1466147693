"""pool_upload_share.server: the server phase's pool upload over the
window, in percent: the program's ``server.pool_upload`` spans, one per
``run_server_phase`` call on the resident path (the pool read from the
store, put on the device, and the state copied for donation).  Nothing
to read on the streamed path, or in a program without the span."""


def read(ctx):
    if ctx.driver != "server":
        return None
    spans = [s.dur_wall for s in ctx.spans if s.name == "server.pool_upload"]
    if not spans:
        return None
    return 100.0 * sum(spans) / ctx.info["window_s"]
