"""`paddle serve --gen_config` script of the `granite-4.0-h-micro`
configuration, and the documented way to serve the model:
``make_decode_model()`` returns Granite-4.0-H-Micro whole
(``paddle_tpu/models/granite_hybrid.py``: 36 Mamba-2 layers whose
recurrent state lives in a state entry a sequence, beside the K/V pages
of four grouped-query attention layers, in one cache manager; bfloat16
weights and pages, float32 state) over the repo's paged decoder, at the
published widths.  **Random weights from a seed; loading a checkpoint is
not supported.**

    scripts/paddle serve \
        --gen_config=perf/configs/granite-4.0-h-micro.gen_config.py \
        --gen_slots=64 --gen_max_tokens=640

Sizes come from ``granite-4.0-h-micro.json`` beside this file.
``PERF_GEN_SEED`` seeds the weights (default 0); ``PERF_GEN_REHEARSE=1``
takes the file's toy ``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.models.granite_hybrid import GraniteHybridLM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "granite-4.0-h-micro.json")) as f:
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
    return GraniteHybridLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=heads, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=head_dim,
        layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
        intermediate_size=cfg["shared_intermediate_size"],
        mamba_n_heads=cfg["mamba_n_heads"],
        mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_n_groups=cfg["mamba_n_groups"],
        rms_norm_eps=cfg["rms_norm_eps"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        max_len=min(cfg["max_position_embeddings"],
                    g["pages_per_seq"] * g["page_size"]),
        num_pages=g["num_pages"], page_size=g["page_size"],
        pages_per_seq=g["pages_per_seq"], state_entries=g["state_entries"],
        dtype=g["dtype"], eos_id=g["eos_id"],
        seed=int(os.environ.get("PERF_GEN_SEED", "0")))
