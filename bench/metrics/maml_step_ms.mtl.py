"""Device time per traced round, in ms, of the ops in the program's
``maml_step`` scope: the MAML meta step of each meta round. The union of
their intervals, averaged over the device planes."""
from bench import program_trace


def read(run):
    return program_trace.scope_ms(run, "maml_step")
