"""Decode engine: share of the device time inside the window's runs of
``jit__decode_step`` that lies under none of the skeleton's scopes, in
%: how whole the split of a step by ``step_mixer_ms``, ``step_mlp_ms``
and ``step_head_ms`` is."""

from perf.harness import skeleton as sk


def read(record):
    return sk.unscoped_share(record, sk.DECODE_PROGRAM, sk.DECODE_MODULE)
