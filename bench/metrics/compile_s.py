"""Seconds of set-up spent in backend compiles or persistent-cache loads."""


def read(run):
    return run.setup_compile_s
