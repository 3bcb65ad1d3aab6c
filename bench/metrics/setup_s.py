"""Set-up seconds: process start to the window's start (data, service,
warm-up versions, probe compiles or cache loads)."""


def read(run):
    return run.setup_s
