"""Device time per traced round, in ms, of the ops in the program's
``telemetry_row`` scope: the per-round metrics row the Eq.-(11) ledger
prices. The union of their intervals, averaged over the device planes."""
from bench import program_trace


def read(run):
    return program_trace.scope_ms(run, "telemetry_row")
