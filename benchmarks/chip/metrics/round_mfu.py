"""round_mfu: the device round's model FLOP/s over the chip's peak.

Samples trained in the traced window (K x H x b per round), times the
configuration's device-block-plus-auxiliary-net training FLOPs per sample
(``flops_per_sample(..., "device_train")``), over the window's seconds
and the ``device_kind``'s bf16 peak, in percent.  The auxiliary
evaluation's forwards are not counted.
"""


def read(ctx):
    if ctx.driver != "device" or not ctx.peak:
        return None
    rate = ctx.info["samples"] / ctx.info["window_s"]
    return 100.0 * rate * ctx.flops("device_train") / \
        ctx.peak["bf16_flops_per_s"]
