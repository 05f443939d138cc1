"""Idle share of the device over the traced window, in percent."""

import trace_reduce


def read(reading: dict):
    trace = reading['trace']
    if not trace or not trace['busy_s']:
        return None
    return trace_reduce.idle_share(trace['busy_s'], trace['window_s'])
