"""Share of the pools the decode lanes' read fetched, in percent: over
the window, the growth of ``serving_lane_pages_read_total{kind}`` (the
pages the lanes' read of a decode or mixed step fetched, summed over
the kind's K/V layers; the program counts them by host arithmetic on
lengths, liveness and ring geometry where it packs the step) over that
of ``serving_lane_pages_pool_total{kind}`` (the pages the same layers'
pools hold), each summed over the kinds. 100 where the lanes sweep
every pool; the live-page walk reads what is live. None where the
program has no such counter."""
from _lib import registry_delta

READ = "serving_lane_pages_read_total{"
POOL = "serving_lane_pages_pool_total{"


def _summed(layers: dict, prefix: str):
    keys = [key for key in layers.get("registry_close") or {}
            if key.startswith(prefix)]
    if not keys:
        return None
    return sum(registry_delta(layers, key) for key in keys)


def read(name: str, layers: dict):
    read_pages = _summed(layers, READ)
    pool_pages = _summed(layers, POOL)
    if read_pages is None or not pool_pages:
        return None
    return 100.0 * read_pages / pool_pages
