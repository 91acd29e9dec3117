"""`paddle serve --gen_config` script of the `olmoe-1b-7b`
configuration, and the documented way to serve the model:
``make_decode_model()`` returns OLMoE (``paddle_tpu/models/olmoe.py``:
RoPE, q/k RMSNorm, 64 SwiGLU experts top-8, bfloat16 weights and K/V
pages) over the repo's paged decoder, at the published widths of
allenai/OLMoE-1B-7B-0125-Instruct, 8 of its 16 layers (what one 16 GB
chip holds beside a cache).  **Random weights from a seed; loading a
checkpoint is not supported yet.**

    scripts/paddle serve \
        --gen_config=perf/configs/olmoe-1b-7b.gen_config.py \
        --gen_slots=32 --gen_max_tokens=256

Sizes come from ``olmoe-1b-7b.json`` beside this file.
``PERF_GEN_SEED`` seeds the weights (default 0); ``PERF_GEN_REHEARSE=1``
takes the file's toy ``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.models.olmoe import OlmoeLM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "olmoe-1b-7b.json")) as f:
        cfg = json.load(f)
    if os.environ.get("PERF_GEN_REHEARSE") == "1":
        cfg = {**cfg, **cfg["rehearse"],
               "generate": {**cfg["generate"],
                            **cfg["rehearse"].get("generate", {})}}
    g = cfg["generate"]
    return OlmoeLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_layers=cfg["num_hidden_layers"],
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["intermediate_size"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        max_len=cfg["max_position_embeddings"], num_pages=g["num_pages"],
        page_size=g["page_size"], pages_per_seq=g["pages_per_seq"],
        dtype=g["dtype"], eos_id=g["eos_id"],
        seed=int(os.environ.get("PERF_GEN_SEED", "0")))
