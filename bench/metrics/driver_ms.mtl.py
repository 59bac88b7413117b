"""Host self time of the MTL drivers per traced round, in ms: the
program's ``repro.driver.*`` spans other than ``repro.driver.sync``
(process, meta_train, adapt_task, setup, dispatch, bill), each less the
spans nested in it. Spans still open when the profiler stops are not in
the trace."""
from bench import program_trace

SPANS = ("repro.driver.process", "repro.driver.meta_train",
         "repro.driver.adapt_task", "repro.driver.setup",
         "repro.driver.dispatch", "repro.driver.bill")


def read(run):
    return program_trace.span_ms(run, SPANS)
