"""How far the device's clock leads the host's in this trace, at
least: the largest (host enqueue start - device run start) over the
program runs both sides stamped with one ``run_id``. A health reading
for whoever lays host spans over device time."""
from _lib import scoped_trace     # puts benchmark/ on the path
import xplane_scopes


def read(name: str, layers: dict):
    lead = xplane_scopes.clock_lead_seconds(scoped_trace(layers))
    return None if lead is None else lead * 1e3
