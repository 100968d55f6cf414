"""Device time of the dense model inside the served program, in ms per
``score`` call: the operations of the traced window under the program's
``bottom_mlp``, ``interaction`` and ``top_mlp`` scopes, summed over the
window and divided by its calls.  On a v5e the fusions that write the
interaction's ``[batch, 351]`` triangle carry no ``tf_op`` and are left
out: about 0.41 ms of a 3.85 s call."""

from bench.scopes import DENSE, keep_scopes, scope_ms

warm = keep_scopes


def read(run):
    return scope_ms(run, DENSE, "calls")
