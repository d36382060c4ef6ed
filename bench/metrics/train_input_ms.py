"""Input pipeline: mean host time per step in ``loader.batch_at`` over the
window (the benchmark's ``bench.loader`` span), in ms."""


def read(run):
    if run.get("kind") != "train":
        return None
    return run["input_ms_per_step"]
