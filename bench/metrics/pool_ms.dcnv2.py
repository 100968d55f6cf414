"""Device time of the multi-hot pooling inside the served DLRM-DCNv2
program, in ms per ``score`` call: the operations of the traced window
under the program's ``bag_pool`` scope (inside ``embedding``: the sum of
each field's gathered rows), each counted by its own time as
``scopes.ScopedTrace.attributed`` counts it, summed over the window and
divided by its calls."""

from bench.scopes import SCOPES, ScopedTrace, _attributed, keep_scopes, \
    scope_of

warm = keep_scopes

POOL = SCOPES + ("bag_pool",)


def read(run):
    tr = run.traces.get("window")
    calls = run.record.get("calls")
    if not isinstance(tr, ScopedTrace) or not calls:
        return None
    total, hit = 0.0, False
    for plane, events in tr.ops.items():
        paths = tr.tf_op.get(plane, {})
        for name, ns, _ in _attributed(events, paths):
            if scope_of(paths.get(name, ""), POOL)[0] == "bag_pool":
                total += ns * 1e-9 / len(tr.ops)
                hit = True
    return total / calls * 1e3 if hit else None
