"""Device time per traced round, in ms, of the ops in the program's
``eq6_mix`` scope: the Eq.-(6) consensus round of a cluster. The union
of their intervals, averaged over the device planes."""
from bench import program_trace


def read(run):
    return program_trace.scope_ms(run, "eq6_mix")
