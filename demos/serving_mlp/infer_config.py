"""Serving MLP inference config (fluid script form).

A relu fc stack ending in a softmax head.  Shipped as a lint/optimize
target so `paddle lint --optimize` exercises the rewrite pipeline +
donation-safety analyzer over the program shape the replica pool
serves — see scripts/lint_self.sh and
tests/test_optimizer.py::test_serving_mlp_demo_config_optimizes_with_bit_parity.

Feed: x (batch, 32).  Fetch: prediction (batch, 10).
"""

import paddle_tpu as fluid

DEPTH = 3
HIDDEN = 64
IN_DIM = 32
CLASSES = 10

x = fluid.layers.data(name="x", shape=[IN_DIM], dtype="float32")
h = x
for _ in range(DEPTH):
    h = fluid.layers.fc(input=h, size=HIDDEN, act="relu")
pred = fluid.layers.fc(input=h, size=CLASSES, act="softmax")

# stable fetch name for the lint harness (fc tmp names are positional)
_out = fluid.default_main_program().global_block().create_var(
    name="prediction", shape=pred.shape, dtype=pred.dtype)
fluid.layers.assign(pred, output=_out)
