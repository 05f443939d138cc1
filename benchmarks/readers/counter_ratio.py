"""One counter over another, times ``scale`` (100 for a share in percent)."""

from benchlib import lookup


def read(reading: dict, num: str, den: str, scale: float = 1.0):
    top = lookup(reading['counters'], num)
    bottom = lookup(reading['counters'], den)
    if top is None or not bottom:
        return None
    return scale * top / bottom
