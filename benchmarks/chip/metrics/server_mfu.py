"""server_mfu: the server phase's model FLOP/s over the chip's peak.

Training samples completed in the traced window, times the configuration's
server-block training FLOPs per sample (``flops_per_sample(...,
"server_train")`` beside the reference: three times the forward, no
recomputation), over the window's seconds and the ``device_kind``'s bf16
peak, in percent.  Nothing to read outside the server phase.
"""


def read(ctx):
    if ctx.driver != "server" or not ctx.peak:
        return None
    rate = ctx.info["samples"] / ctx.info["window_s"]
    return 100.0 * rate * ctx.flops("server_train") / \
        ctx.peak["bf16_flops_per_s"]
