"""jit_share.round: compile work over the window, in percent: the
``jit_s`` the program's compile counter put on the window's spans (the
seconds of jit traces, lowerings and backend compiles, persistent-cache
loads included, each counted once), summed, over the window's seconds.
Nothing to read in a program without the counter, which also has no
``device.phase`` span."""


def read(ctx):
    if ctx.driver != "device":
        return None
    if not any(s.name == "device.phase" for s in ctx.spans):
        return None
    jit_s = sum(s.attrs.get("jit_s", 0.0) for s in ctx.spans)
    return 100.0 * jit_s / ctx.info["window_s"]
