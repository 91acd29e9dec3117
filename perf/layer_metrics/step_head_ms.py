"""Decode engine: device time of the decode step's instructions under
the skeleton's ``blk_embed`` and ``blk_head`` scopes (the embedding of
the slots' tokens; the final norm, the vocabulary-wide head and the
greedy choice over it), per decode step, in ms."""

from perf.harness import skeleton as sk
from perf.harness.readers import registry_count


def read(record):
    return sk.part_ms(record, sk.DECODE_PROGRAM, sk.DECODE_MODULE,
                      ["embed", "head"],
                      registry_count(record, "decode_steps_total"))
