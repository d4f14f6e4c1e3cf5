"""Set-up: process start to the window's opening (loading, weights made on
the device, compiling or loading every program from the cache, contexts
registered, every shape of the cell warmed)."""


def read(run):
    return run.setup_s
