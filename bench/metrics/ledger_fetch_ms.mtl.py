"""Host time per traced round, in ms, copying each chunk's telemetry
rows to the host for the Eq.-(11) ledger (span
``repro.telemetry.fetch``)."""
from bench import program_trace


def read(run):
    return program_trace.span_ms(run, "repro.telemetry.fetch")
