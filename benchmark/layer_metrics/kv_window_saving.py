"""What bounding the window layers' cache saves: over the window's
steps, the rows ONE sliding layer had to hold and read for the
decoding slots (``serving_kv_rows_read_total{kind=window}``: at most
the window a slot) over what it would hold uncapped — the rows a full
layer holds (``{kind=full}``). 1 while no sequence has passed the
window, under 1 once rings wrap."""
from _lib import registry_delta
from _trinity import ROWS


def read(name: str, layers: dict):
    window = registry_delta(layers, ROWS % "window")
    full = registry_delta(layers, ROWS % "full")
    if window is None or not full:
        return None
    return window / full
