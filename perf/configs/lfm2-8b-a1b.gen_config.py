"""`paddle serve --gen_config` script of the `lfm2-8b-a1b` configuration,
and the documented way to serve the model: ``make_decode_model()``
returns stage 0 of LFM2-8B-A1B's two-stage pipeline
(``paddle_tpu/models/lfm2_moe.py``: nine gated short-conv layers whose
whole per-sequence state is a two-row conv tail in a state entry, beside
the K/V pages of three RoPE grouped-query attention layers, in one cache
manager; two dense feed-forwards, then ten sigmoid-routed layers of all
32 experts; bfloat16 weights, pages and tails) over the repo's paged
decoder, at the published widths.  **Random weights from a seed; loading
a checkpoint is not supported.**

    scripts/paddle serve \
        --gen_config=perf/configs/lfm2-8b-a1b.gen_config.py \
        --gen_slots=64 --gen_max_tokens=384

Sizes come from ``lfm2-8b-a1b.json`` beside this file.
``PERF_GEN_SEED`` seeds the weights (default 0); ``PERF_GEN_REHEARSE=1``
takes the file's toy ``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.models.lfm2_moe import Lfm2MoeLM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    if os.environ.get("PERF_GEN_REHEARSE") == "1":
        cfg = {**cfg, **cfg["rehearse"],
               "generate": {**cfg["generate"],
                            **cfg["rehearse"].get("generate", {})}}
    g = cfg["generate"]
    heads = cfg["num_attention_heads"]
    # head_dim is not in config.json: hidden_size / num_attention_heads
    # (64); the toy sizes name the published head themselves
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    return Lfm2MoeLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=heads, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=head_dim,
        layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
        num_dense_layers=cfg["num_dense_layers"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        route_eps=g["route_eps"], conv_L_cache=cfg["conv_L_cache"],
        norm_eps=cfg["norm_eps"], rope_theta=cfg["rope_theta"],
        max_len=min(cfg["max_position_embeddings"],
                    g["pages_per_seq"] * g["page_size"]),
        num_pages=g["num_pages"], page_size=g["page_size"],
        pages_per_seq=g["pages_per_seq"], state_entries=g["state_entries"],
        prefill_rows=g["prefill_rows"], chunk_rows=g["chunk_rows"],
        dtype=g["dtype"], eos_id=g["eos_id"],
        seed=int(os.environ.get("PERF_GEN_SEED", "0")))
