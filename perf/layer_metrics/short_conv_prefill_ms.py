"""Gated short-conv layers: device time of a prefill's instructions
under ``short_conv`` (both gates and the causal conv), all conv layers,
in the bucket programs and in the chunk programs that follow the top
bucket, per admission (``decode_prefill_seconds``' count: one a prompt,
whatever its chunks), in ms."""

from perf.harness import short_conv as sc
from perf.harness.readers import registry_count


def read(record):
    parts = [sc.scope_seconds(record, sc.PREFILL_PROGRAMS, sc.PREFILL_MODULE,
                              sc.ANY_SCOPE),
             sc.scope_seconds(record, sc.CHUNK_PROGRAMS, sc.CHUNK_MODULE,
                              sc.ANY_SCOPE)]
    admissions = registry_count(record, "decode_prefill_seconds")
    if not parts[0] or not admissions:
        return None
    return sum(p[0] for p in parts if p) / admissions * 1e3
