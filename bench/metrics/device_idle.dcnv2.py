"""Share of the traced window in which no operation ran on the device, in
%: one minus the union of the device's operation intervals over the
window's length."""


def read(run):
    tr = run.traces.get("window")
    if tr is None or not run.window_s or not tr.ops:
        return None
    return (1.0 - tr.busy_s() / run.window_s) * 100
