"""Decode engine: share of the device time inside the window's runs of
``jit__prefill_bucket`` that lies under none of the skeleton's five
scopes (instructions with no ``op_name``, async ``-done``s, the few the
skeleton reckons between its parts), in %: how whole the split of a
prefill by ``prefill_mixer_ms``, ``prefill_mlp_ms`` and
``prefill_ends_ms`` is."""

from perf.harness import skeleton as sk


def read(record):
    return sk.unscoped_share(record, sk.PREFILL_PROGRAMS, sk.PREFILL_MODULE)
