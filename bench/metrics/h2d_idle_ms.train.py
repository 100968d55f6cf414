"""Device idle time in which the train step waited for its batch, in ms per
step: the idle under the program's ``train.h2d`` host spans (the batch
fetched and its copy started), plus the idle from the end of each step's
``train.run`` span (the dispatch) to the start of its ``train_step`` run,
while the copy is still in flight (``scopes.ScopedTrace.input_wait_s``),
over the traced window, divided by its steps."""

from bench.scopes import input_wait_ms, keep_scopes

warm = keep_scopes


def read(run):
    return input_wait_ms(run, "train.h2d", "train.run", "train_step",
                         "steps")
