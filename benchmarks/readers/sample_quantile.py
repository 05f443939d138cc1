"""A quantile of one of the run's sample lists."""

from benchlib import lookup, quantile


def read(reading: dict, samples: str, q: float):
    values = lookup(reading['counters'], samples)
    return quantile(values, q) if values else None
