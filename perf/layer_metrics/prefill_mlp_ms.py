"""Decode engine: device time of a bucketed prefill's instructions
under the skeleton's ``blk_mlp`` scope (every layer's feed-forward; the
``moe_*`` and ``moe_shared`` scopes lie under it), all layers, per run
of ``jit__prefill_bucket``, in ms."""

from perf.harness import skeleton as sk


def read(record):
    return sk.part_ms(record, sk.PREFILL_PROGRAMS, sk.PREFILL_MODULE,
                      ["mlp"])
