"""Share of dispatched rows that were padding, in percent: 1 - rows scanned /
sum(dispatches x capacity), from the executable ledger's dispatches per
capacity over the window and the rows the window scanned."""


def read(reading: dict):
    counters = reading['counters']
    dispatched = sum(int(capacity) * n for capacity, n in
                     counters.get('dispatches', {}).items())
    if not dispatched or 'rows_scanned' not in counters:
        return None
    return 100.0 * (1.0 - counters['rows_scanned'] / dispatched)
