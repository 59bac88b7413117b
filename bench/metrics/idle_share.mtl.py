"""Share of the traced window of the MTL process in which no operation
ran on the device, in percent."""
from bench import trace


def read(run):
    return trace.idle_share(run.events, *run.window_ns)
