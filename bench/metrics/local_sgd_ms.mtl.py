"""Device time per traced round, in ms, of the ops in the program's
``local_sgd`` scope: each robot's clipped local SGD steps in an FL
round. The union of their intervals, averaged over the device planes."""
from bench import program_trace


def read(run):
    return program_trace.scope_ms(run, "local_sgd")
