"""Device time of the embedding layer inside the served DLRM-DCNv2
program, in ms per ``score`` call: the operations of the traced window
under the program's ``embedding`` scope (hashing the B·ΣL ids' blocks, the
block gather, the ``bag_pool`` sum to B·F rows), summed over the window
and divided by its calls."""

from bench.scopes import keep_scopes, scope_ms

warm = keep_scopes


def read(run):
    return scope_ms(run, ("embedding",), "calls")
