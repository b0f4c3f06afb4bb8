"""Share of its slot's table a prefill chunk's read of its prior context
fetched, in percent: over the window, the growth of
``serving_chunk_pages_read_total{kind}`` (the pages the chunks' reads
fetched, summed over the kind's K/V layers; the program counts them by
host arithmetic on each chunk's start and the pools' geometry where it
packs the chunk) over that of ``serving_chunk_pages_table_total{kind}``
(the pages a gather of the slot's table reads — a window layer's whole
ring — at the same chunks), each summed over the kinds. 100 where the
chunk gathers its table; the chunk walk reads the pages before the
chunk's start. None where the program has no such counter."""
from _lib import registry_delta

READ = "serving_chunk_pages_read_total{"
TABLE = "serving_chunk_pages_table_total{"


def _summed(layers: dict, prefix: str):
    keys = [key for key in layers.get("registry_close") or {}
            if key.startswith(prefix)]
    if not keys:
        return None
    return sum(registry_delta(layers, key) for key in keys)


def read(name: str, layers: dict):
    read_pages = _summed(layers, READ)
    table_pages = _summed(layers, TABLE)
    if read_pages is None or not table_pages:
        return None
    return 100.0 * read_pages / table_pages
