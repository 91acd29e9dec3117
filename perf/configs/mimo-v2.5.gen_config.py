"""`paddle serve --gen_config` script of the `mimo-v2.5` configuration,
and the documented way to serve the model: ``make_decode_model()``
returns one chip's share of MiMo-V2.5 (``paddle_tpu/models/mimo_v2.py``:
64 query heads with q/k heads of 192 and v heads of 128; full layers of
4 K/V heads on a page run beside window-128 layers of 8 K/V heads on
rings with a sink in their softmax; RoPE on 64 of 192 channels; the
sigmoid router over the published 256 experts of which 16 are held, no
shared expert; 1/8 of the vocabulary; bfloat16 weights, pages and rings)
over the repo's paged decoder, at the published widths, layer 0 and the
six layers that follow it.  A prompt over 8,192 rows is prefilled in
4,096-row chunks over its rings.  **Random weights from a seed; loading
a checkpoint is not supported.**

    scripts/paddle serve \
        --gen_config=perf/configs/mimo-v2.5.gen_config.py \
        --gen_slots=48 --gen_max_tokens=2048

Sizes come from ``mimo-v2.5.json`` beside this file.  ``PERF_GEN_SEED``
seeds the weights (default 0); ``PERF_GEN_REHEARSE=1`` takes the file's
toy ``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.models.mimo_v2 import FULL, WINDOW, MimoV2LM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "mimo-v2.5.json")) as f:
        cfg = json.load(f)
    if os.environ.get("PERF_GEN_REHEARSE") == "1":
        cfg = {**cfg, **cfg["rehearse"],
               "generate": {**cfg["generate"],
                            **cfg["rehearse"].get("generate", {})}}
    g = cfg["generate"]
    layers = cfg["num_hidden_layers"]       # the first of the published
    held = cfg["n_routed_experts"]          # this rank's contiguous range
    # partial_rotary_factor x head_dim, to the even number below (64);
    # the toy sizes name it themselves
    rotary = cfg.get("rotary_dim") or int(
        cfg["partial_rotary_factor"] * cfg["head_dim"]) // 2 * 2
    return MimoV2LM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        swa_num_kv_heads=cfg["swa_num_key_value_heads"],
        head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
        rotary_dim=rotary,
        layer_types=[WINDOW if w else FULL
                     for w in cfg["hybrid_layer_pattern"][:layers]],
        mlp_layer_types=["sparse" if r else "dense"
                         for r in cfg["moe_layer_freq"][:layers]],
        sliding_window=cfg["sliding_window"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        num_experts_published=cfg["n_routed_experts_published"],
        held_experts=(cfg["ep_rank"] * held, held),
        experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"] or 1.0,
        rms_norm_eps=cfg["layernorm_epsilon"],
        rope_theta=cfg["rope_theta"], swa_rope_theta=cfg["swa_rope_theta"],
        attention_value_scale=cfg["attention_value_scale"],
        max_len=min(cfg["max_position_embeddings"],
                    g["pages_per_seq"] * g["page_size"]),
        num_pages=g["num_pages"], page_size=g["page_size"],
        pages_per_seq=g["pages_per_seq"], ring_entries=g["ring_entries"],
        prefill_rows=g["prefill_rows"], chunk_rows=g["chunk_rows"],
        dtype=g["dtype"], eos_id=g["eos_id"],
        seed=int(os.environ.get("PERF_GEN_SEED", "0")))
