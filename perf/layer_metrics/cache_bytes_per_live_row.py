"""Two cache lifetimes: resident K/V bytes over live rows, the mean of
the window's samples of the ``decode_cache_rows`` gauges: (rows the
full layers hold + rows the rings hold) x bytes a row over the seated
sequences' rows.  With every layer keeping every row it would read
layers x bytes a row (7 x 4,096 = 28,672 here)."""


def read(record):
    samples = record.get("cache_rows")
    if not samples or not record.get("kv_row_bytes"):
        return None
    per = [(full + ring) * record["kv_row_bytes"]
           / (full / record["full_layers"]) for full, ring in samples]
    return sum(per) / len(per)
