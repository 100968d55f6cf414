"""Device time of the interaction inside the served DLRM-DCNv2 program, in
ms per ``score`` call: the operations of the traced window under the
program's ``interaction`` scope (building x0, the low-rank cross layers
under ``interaction/cross``), summed over the window and divided by its
calls."""

from bench.scopes import keep_scopes, scope_ms

warm = keep_scopes


def read(run):
    return scope_ms(run, ("interaction",), "calls")
