"""Share of the traced window in which no op ran on the device
(mean over the chips): 1 - busy / window."""


def read(name: str, layers: dict):
    if not layers.get("window_s"):
        return None
    return 100.0 * (1.0 - layers["busy_s"] / layers["window_s"])
