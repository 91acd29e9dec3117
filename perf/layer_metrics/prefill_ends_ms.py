"""Decode engine: device time of a bucketed prefill's instructions
under the skeleton's ``blk_embed``, ``blk_head`` and ``blk_store``
scopes (the embedding, the head on the prompt's last row, what the
layers keep of the prompt written to the pools), per run of
``jit__prefill_bucket``, in ms."""

from perf.harness import skeleton as sk


def read(record):
    return sk.part_ms(record, sk.PREFILL_PROGRAMS, sk.PREFILL_MODULE,
                      ["embed", "head", "store"])
