"""A chunk's attention over the cached run: its model FLOPs (4 x query
heads x head size a (query row, key row) pair and attention layer, the
pairs from ``decode_prefill_chunk_pairs_total``: a chunk's real rows x
the rows done before it plus its own causal part) over the device time
under ``attn_chunk`` in the chunk programs (the gather of the run's rows
by the table, the two flash calls and their merge), as a share of the
chip's bf16 peak.  Bound: FLOP/s.  The kernel also multiplies a chunk's
padding rows: in the time, not in the FLOPs."""

from perf.harness import short_conv as sc


def read(record):
    sizes = sc.attention_sizes(record)
    got = sc.scope_seconds(record, sc.CHUNK_PROGRAMS, sc.CHUNK_MODULE,
                           sc.CHUNK_ATTENTION_SCOPE)
    pairs = sc.counted(record, sc.CHUNK_PAIRS)
    if not sizes or not got or not pairs:
        return None
    return (100.0 * sc.chunk_attention_flops(pairs, *sizes) / got[0]
            / record["peaks"]["bf16_flops_per_s"])
