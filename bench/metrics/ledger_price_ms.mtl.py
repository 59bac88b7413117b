"""Host time per traced round, in ms, pricing each chunk's telemetry
rows in float64 into the ledger's buffer and sinks (span
``repro.telemetry.price``)."""
from bench import program_trace


def read(run):
    return program_trace.span_ms(run, "repro.telemetry.price")
