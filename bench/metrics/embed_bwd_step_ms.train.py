"""Device time of the embedding layer's backward inside the train step, in
ms per step: the operations of the traced window under the program's
``embedding`` scope inside ``transpose(`` (hashing the slots again, the
scatter-add of the cotangent into the ROBE array), summed and divided by
the window's steps."""

from bench.scopes import keep_scopes, scope_ms

warm = keep_scopes


def read(run):
    return scope_ms(run, ("embedding",), "steps", backward=True)
