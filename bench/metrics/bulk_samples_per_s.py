"""Samples scored in the window's whole calls over their wall time."""


def read(run):
    if run.traffic["driver"] != "bulk" or not run.record.get("elapsed_s"):
        return None
    return run.record["samples"] / run.record["elapsed_s"]
