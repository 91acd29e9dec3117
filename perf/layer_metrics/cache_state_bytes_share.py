"""Two resources a sequence: the recurrent states' bytes over the
states' plus the live K/V rows' bytes, the mean of the window's samples
of the ``decode_cache_bytes`` gauges with a sequence seated, in %.  A
state entry is the same size whatever the sequence's length; the K/V
rows grow with it."""


def read(record):
    samples = record.get("cache_bytes")
    if not samples:
        return None
    per = [100.0 * state / (state + full) for full, state in samples]
    return sum(per) / len(per)
