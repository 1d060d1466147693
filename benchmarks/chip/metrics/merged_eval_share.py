"""merged_eval_share: the program's ``merged_eval`` spans (the per-epoch
evaluation of the merged model) over the window, in percent."""


def read(ctx):
    if ctx.driver != "server":
        return None
    spent = sum(s.dur_wall for s in ctx.spans if s.name == "merged_eval")
    return 100.0 * spent / ctx.info["window_s"]
