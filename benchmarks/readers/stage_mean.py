"""Mean seconds per call of one stage of the program's stage histogram
(``observability/device.py``), over the window: total / count."""


def read(reading: dict, stage: str):
    entry = reading['counters'].get('stages', {}).get(stage)
    if not entry or not entry.get('count'):
        return None
    return entry['total_s'] / entry['count']
