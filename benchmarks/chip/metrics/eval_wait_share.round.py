"""eval_wait_share.round: the auxiliary evaluation's waits on the device
over the window, in percent: the program's ``eval.wait`` spans (each
eval batch's host read of its loss and accuracy) whose parent is an
``aux_eval`` span.  At most ``aux_eval_share``: the rest of the
evaluation is its host loop, batch uploads and dispatch.  Nothing to
read in a program without the span."""


def read(ctx):
    if ctx.driver != "device":
        return None
    evals = {getattr(s, "span_id", None) for s in ctx.spans
             if s.name == "aux_eval"} - {None}
    waits = [s.dur_wall for s in ctx.spans if s.name == "eval.wait"
             and getattr(s, "parent_id", None) in evals]
    if not waits:
        return None
    return 100.0 * sum(waits) / ctx.info["window_s"]
