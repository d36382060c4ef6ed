"""Device: share of the traced window in which no operation ran, mean over
the cell's chips, in %."""


def read(run):
    if run.get("kind") != "train" or not run.get("trace"):
        return None
    return 100.0 * run["trace"]["idle_share"]
