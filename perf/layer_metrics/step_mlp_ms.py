"""Decode engine: device time of the decode step's instructions under
the skeleton's ``blk_mlp`` scope (every layer's feed-forward; the
``moe_*`` and ``moe_shared`` scopes lie under it), all layers, per
decode step, in ms."""

from perf.harness import skeleton as sk
from perf.harness.readers import registry_count


def read(record):
    return sk.part_ms(record, sk.DECODE_PROGRAM, sk.DECODE_MODULE,
                      ["mlp"], registry_count(record, "decode_steps_total"))
