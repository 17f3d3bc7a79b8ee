"""Executables obtained (compiled or loaded from the persistent cache)
from the window's start until its last request was answered."""


def read(record):
    return record["compiles"]
