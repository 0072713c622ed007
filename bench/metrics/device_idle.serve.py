"""Share of the traced window in which no operation runs on a chip,
averaged over the cell's chips, while the host loop admits requests and
runs decode chunks. Percent."""
from bench import trace as tr


def read(x):
    t = x.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s(t) / t.window_s)
