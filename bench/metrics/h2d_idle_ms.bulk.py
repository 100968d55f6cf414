"""Device idle time in which the served program waited for its batch, in
ms per ``score`` call: the idle under the program's ``serve.h2d`` host
spans, plus the idle from the end of each call's ``serve.run`` span (the
dispatch) to the start of its ``serve_scores`` run, while the batch's copy
is still in flight (``scopes.ScopedTrace.input_wait_s``), over the traced
window, divided by its calls."""

from bench.scopes import input_wait_ms, keep_scopes

warm = keep_scopes


def read(run):
    return input_wait_ms(run, "serve.h2d", "serve.run", "serve_scores",
                         "calls")
