"""Traces, backend compiles and persistent-cache reads JAX reported
inside the window, plus the growth of the drivers' retrace counters
(``repro.core.scanloop.TRACE_COUNTS``). Should be 0."""


def read(run):
    return float(run.compiles_in_window)
