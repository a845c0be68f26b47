"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, averaged over the chips used,
over the same closed loop of whole jobs as the timed run."""


def read(r):
    busy = r.busy_s
    return 100.0 * (1.0 - busy / r.window_s) if busy > 0 else None
