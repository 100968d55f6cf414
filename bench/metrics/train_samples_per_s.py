"""Samples of the window's completed train steps over their wall time."""


def read(run):
    if run.traffic["driver"] != "train" or not run.record.get("elapsed_s"):
        return None
    return run.record["samples"] / run.record["elapsed_s"]
