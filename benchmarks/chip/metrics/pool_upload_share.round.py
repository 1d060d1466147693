"""pool_upload_share.round: the device phase's pool upload over the
window, in percent: the program's ``device.pool_upload`` spans, one per
``run_device_phase`` call (every client's samples pooled on the host and
put on the device, and the round state copied for donation).  Nothing
to read in a program without the span."""


def read(ctx):
    if ctx.driver != "device":
        return None
    spans = [s.dur_wall for s in ctx.spans if s.name == "device.pool_upload"]
    if not spans:
        return None
    return 100.0 * sum(spans) / ctx.info["window_s"]
