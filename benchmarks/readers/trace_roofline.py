"""A kernel's share of its bytes roofline, in percent: the least time the
chip could take to move the dispatch's bytes (``benchmarks/bytes.py``) at the
HBM peak of ``benchmarks/peaks.json``, over the mean device duration of the
program whose name starts with ``module``.  A device kind that the table of
peaks does not have is an error, not a default."""

import trace_reduce
from benchlib import lookup


def read(reading: dict, module: str, bytes: str):
    if not reading['trace']:
        return None
    runs = trace_reduce.module_seconds(reading['trace']['events'], module)
    moved = lookup(reading['counters'], bytes)
    if not runs or not moved:
        return None
    if reading['peaks'] is None:
        raise KeyError(f'benchmarks/peaks.json has no device kind '
                       f'{reading["device_kind"]!r}')
    least_s = moved / reading['peaks']['hbm_bytes_per_s']
    return 100.0 * least_s / (sum(runs) / len(runs))
