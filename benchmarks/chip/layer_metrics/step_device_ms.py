"""Device busy milliseconds inside one train step: per execution of the step
program in the trace, the union of its operations; median over the traced
steps and devices."""

from chipbench import trace


def read(sample):
    if sample["trace_plain"] is None or "step_module" not in sample:
        return None
    return trace.median(trace.module_busy_ms(sample["trace_plain"], sample["step_module"]))
