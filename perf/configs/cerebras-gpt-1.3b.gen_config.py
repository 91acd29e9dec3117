"""`paddle serve --gen_config` script of the `cerebras-gpt-1.3b`
configuration: ``make_decode_model()`` returns the repo's paged decoder
LM (``paddle_tpu/decode/model.py TinyDecoderLM``) at the published
widths of Cerebras-GPT 1.3B, all 24 layers, random weights from a seed.

    scripts/paddle serve \
        --gen_config=perf/configs/cerebras-gpt-1.3b.gen_config.py \
        --gen_slots=16 --gen_max_tokens=256

Sizes come from ``cerebras-gpt-1.3b.json`` beside this file.
``PERF_GEN_SEED`` seeds the weights (default 0); ``PERF_GEN_REHEARSE=1``
takes the file's toy ``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.decode.model import TinyDecoderLM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "cerebras-gpt-1.3b.json")) as f:
        cfg = json.load(f)
    if os.environ.get("PERF_GEN_REHEARSE") == "1":
        cfg = {**cfg, **cfg["rehearse"],
               "generate": {**cfg["generate"],
                            **cfg["rehearse"].get("generate", {})}}
    g = cfg["generate"]
    return TinyDecoderLM(
        vocab=cfg["vocab_size"], d_model=cfg["n_embd"],
        num_heads=cfg["n_head"], num_layers=cfg["n_layer"],
        max_len=cfg["n_positions"], num_pages=g["num_pages"],
        page_size=g["page_size"], pages_per_seq=g["pages_per_seq"],
        eos_id=g["eos_id"], seed=int(os.environ.get("PERF_GEN_SEED", "0")))
