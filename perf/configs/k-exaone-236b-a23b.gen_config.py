"""`paddle serve --gen_config` script of the `k-exaone-236b-a23b`
configuration, and the documented way to serve the model:
``make_decode_model()`` returns one chip's share of K-EXAONE-236B-A23B
(``paddle_tpu/models/exaone_moe.py``: 64 query heads on 8 K/V heads,
window-128 RoPE layers on rings beside a NoPE full layer, the sigmoid
router over the published 128 experts of which 16 are held beside a
shared expert, 1/8 of the vocabulary, bfloat16 weights and K/V pages)
over the repo's paged decoder, at the published widths, layer 0 and the
six layers that follow it.  **Random weights from a seed; loading a
checkpoint is not supported.**

    scripts/paddle serve \
        --gen_config=perf/configs/k-exaone-236b-a23b.gen_config.py \
        --gen_slots=64 --gen_max_tokens=512

Sizes come from ``k-exaone-236b-a23b.json`` beside this file.
``PERF_GEN_SEED`` seeds the weights (default 0); ``PERF_GEN_REHEARSE=1``
takes the file's toy ``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.models.exaone_moe import ExaoneMoeLM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "k-exaone-236b-a23b.json")) as f:
        cfg = json.load(f)
    if os.environ.get("PERF_GEN_REHEARSE") == "1":
        cfg = {**cfg, **cfg["rehearse"],
               "generate": {**cfg["generate"],
                            **cfg["rehearse"].get("generate", {})}}
    g = cfg["generate"]
    layers = cfg["num_hidden_layers"]       # the first of the published
    held = cfg["num_experts"]               # this rank's contiguous range
    return ExaoneMoeLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=cfg["layer_types"][:layers],
        mlp_layer_types=cfg["mlp_layer_types"][:layers],
        sliding_window=cfg["sliding_window"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        num_experts_published=cfg["num_experts_published"],
        held_experts=(cfg["ep_rank"] * held, held),
        experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        max_len=min(cfg["max_position_embeddings"],
                    g["pages_per_seq"] * g["page_size"]),
        num_pages=g["num_pages"], page_size=g["page_size"],
        pages_per_seq=g["pages_per_seq"], dtype=g["dtype"],
        eos_id=g["eos_id"],
        seed=int(os.environ.get("PERF_GEN_SEED", "0")))
