"""Executables obtained (compiled or loaded from the persistent cache)
inside the window."""


def read(record):
    return record["compiles"]
