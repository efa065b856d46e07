"""step_mfu.gap, step_mfu.tok (%, higher is better; layer: whole step
against the chip). Least time the chip needs for the traced window's work
(the larger of FLOPs over the peak and bytes over the bandwidth; weights
once per executed step, each context's K/V once) over the traced wall time
times the chips. The window's totals are used, which is never more than
the sum of the steps' own least times."""

from harness import costs, readers


def read(run):
    w, ws = readers.serve_work(run), readers.window_s(run)
    if run.peaks is None or w is None or not w["tokens"] or not ws:
        return None
    least, bound = costs.least_seconds(w["flops"], w["bytes"], run.peaks)
    run.note(metric="step_mfu", least_seconds=least, bound=bound,
             window_s=ws, steps=len(readers.steps(run)), **w)
    return 100.0 * least / (ws * run.chips)
