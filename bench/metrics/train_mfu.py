"""EM step: the benchmark's operation count per row times the rows whose
step lay wholly inside the traced window (its ``bench.step`` spans), over
that window's length and the chips' bf16 peak, in %.  The profiler's start
and stop lie outside the spans it counts."""


def read(run):
    trace = run.get("trace")
    if run.get("kind") != "train" or not trace or not run.get("peak"):
        return None
    steps = trace["spans_inside"].get("bench.step", 0)
    if not steps or trace["window_s"] <= 0:
        return None
    rows_per_s = steps * run["batch"] / trace["window_s"]
    flops_per_s = rows_per_s * run["work"]["train_flops"]
    return 100.0 * flops_per_s / (run["chips"] * run["peak"]["bf16_flops_per_s"])
