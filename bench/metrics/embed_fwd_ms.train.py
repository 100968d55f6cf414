"""Device time of the embedding layer's forward inside the train step, in
ms per step: the operations of the traced window under the program's
``embedding`` scope and not inside ``transpose(`` (hashing the slots, the
gather from the ROBE array), summed and divided by the window's steps."""

from bench.scopes import keep_scopes, scope_ms

warm = keep_scopes


def read(run):
    return scope_ms(run, ("embedding",), "steps", backward=False)
