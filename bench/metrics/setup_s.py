"""Set-up time: process start to the window's start (imports, the
program's inputs, compiling or loading every program, one pass of the
request cycle)."""


def read(run: dict):
    return run["setup_s"]
