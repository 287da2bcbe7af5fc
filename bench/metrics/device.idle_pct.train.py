"""Share of the traced training steps in which no operation ran on the
device, averaged over the cell's devices (each device's share is logged
on an earlier line)."""


def read(run, trace, peaks):
    if trace is None or not trace.device_ids or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
