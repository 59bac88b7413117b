"""Host time per traced round, in ms, in which the MTL drivers wait for
the chunk outputs they read (span ``repro.driver.sync``)."""
from bench import program_trace


def read(run):
    return program_trace.span_ms(run, "repro.driver.sync")
