"""Seconds the backend compiled before the window opened, for the
executables the persistent cache did not answer (or with it off):
``compile_seconds{stage=compile}``; 0.0 where every one was a
load."""
from _setup import stage_total


def read(name: str, layers: dict):
    return stage_total(layers, ("compile",))
