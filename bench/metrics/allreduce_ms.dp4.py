"""Device time of the data-parallel train step's all-reduce operations, in
ms per step: the operations of the traced window whose HLO is an
all-reduce (the sum of the gradients over the chips, in whatever pieces
and start/done halves the compiler splits it), summed on each chip,
averaged over the chips and divided by the window's steps."""


def read(run):
    tr = run.traces.get("window")
    steps = run.record.get("steps")
    if tr is None or not steps or not tr.ops:
        return None
    per = [sum(d for name, _, d in events if "all-reduce" in name)
           for events in tr.ops.values()]
    if not any(per):
        return None
    return sum(per) / len(per) * 1e-9 / steps * 1e3
