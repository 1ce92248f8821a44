"""Per-layer metric ``device_idle_share`` (device): share of the traced
window in which no operation ran on the device, in %."""

from readers import share


def read(m):
    return share(m.trace.window_s - m.trace.busy_s, m.trace.window_s)
