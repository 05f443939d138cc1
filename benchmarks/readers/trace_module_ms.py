"""Mean device duration, in milliseconds, of one run of the program whose
name starts with ``module``, from the profiler trace."""

import trace_reduce


def read(reading: dict, module: str):
    if not reading['trace']:
        return None
    runs = trace_reduce.module_seconds(reading['trace']['events'], module)
    return 1000.0 * sum(runs) / len(runs) if runs else None
