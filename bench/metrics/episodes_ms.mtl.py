"""Device time per traced round, in ms, of the ops in the program's
``episodes`` scope: the ε-greedy rollouts resampled into minibatches, of
the meta and the FL rounds. The union of their intervals, averaged over
the device planes."""
from bench import program_trace


def read(run):
    return program_trace.scope_ms(run, "episodes")
