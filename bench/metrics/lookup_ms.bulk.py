"""Device time of the embedding layer inside the served program, in ms per
``score`` call: the operations of the traced window under the program's
``embedding`` scope (hashing the slots, the gather from the ROBE array and
its cast), summed over the window and divided by its calls."""

from bench.scopes import keep_scopes, scope_ms

warm = keep_scopes


def read(run):
    return scope_ms(run, ("embedding",), "calls")
