"""Seconds the persistent compilation cache took to give executables
back before the window opened: ``compile_seconds{stage=load}``."""
from _setup import stage_total


def read(name: str, layers: dict):
    return stage_total(layers, ("load",))
