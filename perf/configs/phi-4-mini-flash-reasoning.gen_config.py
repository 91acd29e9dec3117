"""`paddle serve --gen_config` script of the `phi-4-mini-flash-reasoning`
configuration, and the documented way to serve the model:
``make_decode_model()`` returns Phi-4-mini-flash-reasoning whole
(``paddle_tpu/models/phi4_flash.py``: nine Mamba-1 layers whose
recurrent state lives in a state entry a sequence, eight window-512
differential-attention layers on rings, ONE full layer's page run that
seven cross layers read beside it, seven gated memory units; bfloat16
weights and pages, float32 state) over the repo's paged decoder, at the
published widths.  **Random weights from a seed; loading a checkpoint is
not supported.**

    scripts/paddle serve \
        --gen_config=perf/configs/phi-4-mini-flash-reasoning.gen_config.py \
        --gen_slots=64 --gen_max_tokens=1024

Sizes come from ``phi-4-mini-flash-reasoning.json`` beside this file
(the ``mamba_*`` sizes, which the published config.json leaves to its
class's defaults, from its ``assumed_sizes``).  ``PERF_GEN_SEED`` seeds
the weights (default 0); ``PERF_GEN_REHEARSE=1`` takes the file's toy
``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.models.phi4_flash import Phi4FlashLM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "phi-4-mini-flash-reasoning.json")) as f:
        cfg = json.load(f)
    if os.environ.get("PERF_GEN_REHEARSE") == "1":
        cfg = {**cfg, **cfg["rehearse"],
               "generate": {**cfg["generate"],
                            **cfg["rehearse"].get("generate", {})}}
    g, sizes = cfg["generate"], cfg["assumed_sizes"]
    heads = cfg["num_attention_heads"]
    if sizes["head_dim"] != cfg["hidden_size"] // heads:
        raise ValueError("head_dim is hidden_size / num_attention_heads")
    return Phi4FlashLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=heads, num_kv_heads=cfg["num_key_value_heads"],
        num_layers=cfg["num_hidden_layers"],
        mb_per_layer=cfg["mb_per_layer"],
        intermediate_size=cfg["intermediate_size"],
        sliding_window=cfg["sliding_window"],
        mamba_d_state=sizes["mamba_d_state"],
        mamba_d_conv=sizes["mamba_d_conv"],
        mamba_expand=sizes["mamba_expand"],
        mamba_dt_rank=sizes["mamba_dt_rank"],
        layer_norm_eps=cfg["layer_norm_eps"],
        max_len=min(cfg["max_position_embeddings"],
                    g["pages_per_seq"] * g["page_size"]),
        num_pages=g["num_pages"], page_size=g["page_size"],
        pages_per_seq=g["pages_per_seq"], state_entries=g["state_entries"],
        dtype=g["dtype"], eos_id=g["eos_id"],
        seed=int(os.environ.get("PERF_GEN_SEED", "0")))
