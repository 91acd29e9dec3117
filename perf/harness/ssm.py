"""State-space layers (Mamba-2, SSD): the bytes and operations the
algorithm needs, computed from shapes and from the program's counters,
for the readers of the ``ssm_*`` metrics.  Kept with the benchmark: a
share of a roofline is these numbers over a device time.

The program's scopes (``paddle_tpu/models/granite_hybrid.py``): ``ssm``
holds a mamba layer's mixer less its two projections; inside it
``ssm_conv`` (both paths), ``ssm_state`` (a decode step's one-token
update of the slots' entries: the ``ssd_step`` kernel) and ``ssm_scan``
(a prefill's chunked recurrence).  The device time under a scope is
``linear_attn.scope_seconds``'s, as for the other hybrid.
"""

from perf.harness.linear_attn import (DECODE_MODULE, DECODE_PROGRAM,  # noqa: F401
                                      PREFILL_MODULE, PREFILL_PROGRAMS,
                                      scope_seconds)

ANY_SCOPE = r"/ssm/"
STATE_SCOPE = r"/ssm_state/"
SCAN_SCOPE = r"/ssm_scan/"
MAMBA = "mamba"


def sizes(record):
    """(mamba layers, heads, a head's channels, state size) of the
    configuration as run, or None for one without such layers."""
    cfg = record["config"]
    if "mamba_d_state" not in cfg:
        return None
    kept = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return (sum(t == MAMBA for t in kept), cfg["mamba_n_heads"],
            cfg["mamba_d_head"], cfg["mamba_d_state"])


def state_bytes(layers, heads, d_head, d_state, itemsize=4):
    """Bytes of one sequence's recurrent states, all mamba layers:
    heads x channels x state size float32 a layer."""
    return float(layers * heads * d_head * d_state * itemsize)


def step_state_bytes(slot_steps, layers, heads, d_head, d_state):
    """Bytes the decode steps have to move for ``slot_steps`` live
    slot-steps: each live slot's states read once and written once a
    step."""
    return 2.0 * slot_steps * state_bytes(layers, heads, d_head, d_state)


def scan_flops(rows, layers, heads, d_head, d_state):
    """FLOPs of the recurrence over ``rows`` rows, whatever the
    chunking: a row decays the state, writes one outer product and
    reads it with C, 2 x channels x state size each, per head and
    layer."""
    return 6.0 * rows * layers * heads * d_head * d_state
