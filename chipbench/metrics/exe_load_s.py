"""Seconds spent getting the program's executables at set-up: for a
campaign the ``CompileReport`` load (or compile) time of every bucket of
the warm-up execution, for the service the construction of
``AnomalyService``, which loads (or compiles) its bucket executables."""


def read(ctx):
    return ctx["counters"].get("exe_load_s")
