"""Device time of the dense model inside the train step, in ms per step:
the operations of the traced window under the program's ``bottom_mlp``,
``interaction`` and ``top_mlp`` scopes, forward and backward, summed and
divided by the window's steps.  On a v5e the interaction's backward
scatters run as ``while`` loops that carry no ``tf_op``; the ops they
hold do, and the loops count under ``interaction`` by them
(``scopes._attributed``)."""

from bench.scopes import DENSE, keep_scopes, scope_ms

warm = keep_scopes


def read(run):
    return scope_ms(run, DENSE, "steps")
