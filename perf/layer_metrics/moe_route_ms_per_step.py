"""Expert layer: device time of the decode step's instructions under
``moe_router`` and ``moe_dispatch`` (the router's matmul and scores;
the group step under ``moe_group``, the top-k, the held range and the
load: two selections over the router's width a layer), all routed
layers, per decode step, in ms."""

from perf.harness import ling_hybrid


def read(record):
    return ling_hybrid.step_scope_ms(record, ling_hybrid.ROUTE_SCOPE)
