"""Set-up: from the process's start to the window's, in seconds (loading,
weights, traffic, compiling or loading compiled programs, warming up)."""


def read(run):
    return run.setup_s
