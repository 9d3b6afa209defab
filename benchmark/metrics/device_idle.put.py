"""device_idle.put: 1 - device busy (union of device-op intervals in
the trace) over the traced window, in percent."""

from lib import readers


def read(ctx):
    return readers.idle_pct(ctx)
