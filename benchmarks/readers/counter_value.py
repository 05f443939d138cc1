"""One counter of the run, by its dotted key."""

from benchlib import lookup


def read(reading: dict, key: str):
    return lookup(reading['counters'], key)
