"""Executables the program obtained before the window opened, loaded
or compiled: the count of ``compile_seconds{stage=load}`` and
``{stage=compile}`` (the serve programs, the harness's weight program,
the small eager ones)."""
from _setup import stage_total


def read(name: str, layers: dict):
    return stage_total(layers, ("load", "compile"), part="count")
