"""Device time of the optimizer inside the train step, in ms per step: the
operations of the traced window under the program's ``optimizer`` scope
(the NaN guard, adagrad's update of every leaf), summed and divided by
the window's steps."""

from bench.scopes import keep_scopes, scope_ms

warm = keep_scopes


def read(run):
    return scope_ms(run, ("optimizer",), "steps")
