"""`paddle serve --gen_config` script of the `olmo-hybrid-7b`
configuration, and the documented way to serve the model:
``make_decode_model()`` returns stage 0 of a two-stage pipeline of
Olmo-Hybrid-7B (``paddle_tpu/models/olmo_hybrid.py``: twelve
Gated-DeltaNet layers whose recurrent state lives in a state entry a
sequence, beside the K/V pages of four full-attention layers, in one
cache manager; bfloat16 weights and pages, float32 state) over the
repo's paged decoder, at the published widths.  **Random weights from a
seed; loading a checkpoint is not supported.**

    scripts/paddle serve \
        --gen_config=perf/configs/olmo-hybrid-7b.gen_config.py \
        --gen_slots=48 --gen_max_tokens=512

Sizes come from ``olmo-hybrid-7b.json`` beside this file.
``PERF_GEN_SEED`` seeds the weights (default 0); ``PERF_GEN_REHEARSE=1``
takes the file's toy ``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.models.olmo_hybrid import OlmoHybridLM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "olmo-hybrid-7b.json")) as f:
        cfg = json.load(f)
    if os.environ.get("PERF_GEN_REHEARSE") == "1":
        cfg = {**cfg, **cfg["rehearse"],
               "generate": {**cfg["generate"],
                            **cfg["rehearse"].get("generate", {})}}
    g = cfg["generate"]
    heads = cfg["num_attention_heads"]
    return OlmoHybridLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=heads,
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
        intermediate_size=cfg["intermediate_size"],
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_len=min(cfg["max_position_embeddings"],
                    g["pages_per_seq"] * g["page_size"]),
        num_pages=g["num_pages"], page_size=g["page_size"],
        pages_per_seq=g["pages_per_seq"], state_entries=g["state_entries"],
        dtype=g["dtype"], eos_id=g["eos_id"],
        seed=int(os.environ.get("PERF_GEN_SEED", "0")))
