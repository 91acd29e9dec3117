"""A decoder-hybrid-decoder (Phi-4-mini-flash-reasoning: Mamba-1 layers,
window rings, ONE full layer's page run that the cross layers read,
gated memory units): the bytes the algorithm needs, computed from shapes
and from the program's counters, for the readers of the
``attn_shared_*``, ``ssm_s6_*`` and ``prefill_cross_rows_share``
metrics.  Kept with the benchmark: a share of a roofline is these
numbers over a device time.

The program's scopes (``paddle_tpu/models/phi4_flash.py``): ``ssm``
holds a Mamba-1 layer's mixer less its in- and out-projections; inside
it ``ssm_conv`` (both paths), ``ssm_state`` (a decode step's one-token
update of the slots' entries: the ``s6_step`` kernel) and ``ssm_scan``
(a prefill's recurrence, row by row); ``attn_shared`` holds the cached
read of the one page run by its owner and by every cross layer (and the
owner's write of the step's row); ``attn_window`` the rings'; ``gmu``
the gated memory units' product.  The device time under a scope is
``linear_attn.scope_seconds``'s, as for the other hybrids.

Its counters: ``decode_prefill_rows_total{part}`` (the rows the bucketed
prefills computed in the layers that fill a cache, ``self``, and in
those that read another layer's, ``cross``: the rows the bucket's
program handed those layers, a shape noted as the program was traced,
so one a prefill that stops half-way down and the bucket's otherwise)
and
``decode_shared_run_reads_total`` (the layers that read the page run,
summed over the decode steps).  Read by ``tick_account.delta``, which
holds to a label; a program without them (a parent commit, another
model) reads None.
"""

from perf.harness import tick_account
from perf.harness.linear_attn import (DECODE_MODULE, DECODE_PROGRAM,  # noqa: F401
                                      PREFILL_MODULE, PREFILL_PROGRAMS,
                                      scope_seconds)
from perf.harness.readers import registry_count

ANY_SCOPE = r"/ssm/"
STATE_SCOPE = r"/ssm_state/"
SCAN_SCOPE = r"/ssm_scan/"
SHARED_SCOPE = r"/attn_shared/"
PREFILL_ROWS = "decode_prefill_rows_total"
SHARED_READS = "decode_shared_run_reads_total"


def sizes(record):
    """(Mamba-1 layers, channels, state size) of the configuration as
    run, or None for one without such layers: a Mamba layer at every
    even index up to ``L / 2``; ``mamba_expand x hidden_size``
    channels."""
    cfg = record["config"]
    given = cfg.get("assumed_sizes", {})
    if cfg.get("model_type") != "phi4flash" or "mamba_d_state" not in given:
        return None
    return (cfg["num_hidden_layers"] // 4 + 1,
            given["mamba_expand"] * cfg["hidden_size"],
            given["mamba_d_state"])


def state_bytes(layers, channels, d_state, itemsize=4):
    """Bytes of one sequence's recurrent states, all Mamba-1 layers:
    channels x state size float32 a layer."""
    return float(layers * channels * d_state * itemsize)


def step_state_bytes(slot_steps, layers, channels, d_state):
    """Bytes the decode steps have to move for ``slot_steps`` live
    slot-steps: each live slot's states read once and written once a
    step."""
    return 2.0 * slot_steps * state_bytes(layers, channels, d_state)


def shared_run_bytes(record):
    """Bytes the window's decode steps had to read of the one page run:
    the live rows' K and V as stored (the driver's ``kv_bytes``: rows x
    bytes a row, ONE layer's) times the layers that read them a step
    (the counter over the steps: the owner and every cross layer).  The
    work counted is the algorithm's, whatever kernel does it.  None
    without the counter or the rows."""
    reads = tick_account.delta(record, SHARED_READS)
    steps = registry_count(record, "decode_steps_total")
    if not reads or not steps or not record.get("kv_bytes"):
        return None
    return record["kv_bytes"] * reads / steps


def prefill_rows(record, part):
    """The window's delta of ``decode_prefill_rows_total{part}``."""
    return tick_account.delta(record, PREFILL_ROWS, part=part)
