"""Device time per traced round, in ms, of the ops in the program's
``greedy_eval`` scope: the greedy running reward of an FL round. The
union of their intervals, averaged over the device planes."""
from bench import program_trace


def read(run):
    return program_trace.scope_ms(run, "greedy_eval")
