"""aux_eval_share: the program's ``aux_eval`` spans (the auxiliary
head's evaluation after each device round) over the window, in
percent."""


def read(ctx):
    if ctx.driver != "device":
        return None
    spent = sum(s.dur_wall for s in ctx.spans if s.name == "aux_eval")
    return 100.0 * spent / ctx.info["window_s"]
