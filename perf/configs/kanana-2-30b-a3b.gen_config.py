"""`paddle serve --gen_config` script of the `kanana-2-30b-a3b`
configuration, and the documented way to serve the model:
``make_decode_model()`` returns one chip's share of Kanana-2-30B-A3B
(``paddle_tpu/models/kanana_mla.py``: multi-head latent attention, 32
heads on ONE stored row a token a layer, 512 + 64 numbers at 640 lanes,
a decode step absorbed through the ``latent_paged_attention`` kernel
and a prefill expanded through the flash kernel; the sigmoid router
over the published 128 experts of which 16 are held beside the shared
expert, 1/8 of the vocabulary, bfloat16 weights and latent rows) over
the repo's paged decoder, at the published widths, layer 0 and the 15
layers that follow it.  **Random weights from a seed; loading a
checkpoint is not supported.**

    scripts/paddle serve \
        --gen_config=perf/configs/kanana-2-30b-a3b.gen_config.py \
        --gen_slots=64 --gen_max_tokens=1021

Sizes come from ``kanana-2-30b-a3b.json`` beside this file.
``PERF_GEN_SEED`` seeds the weights (default 0); ``PERF_GEN_REHEARSE=1``
takes the file's toy ``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.models.kanana_mla import KananaMlaLM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "kanana-2-30b-a3b.json")) as f:
        cfg = json.load(f)
    if os.environ.get("PERF_GEN_REHEARSE") == "1":
        cfg = {**cfg, **cfg["rehearse"],
               "generate": {**cfg["generate"],
                            **cfg["rehearse"].get("generate", {})}}
    g = cfg["generate"]
    held = cfg["n_routed_experts"]          # this rank's contiguous range
    return KananaMlaLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_layers=cfg["num_hidden_layers"],    # the first of the published
        first_k_dense_replace=cfg["first_k_dense_replace"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["n_shared_experts"],
        num_experts_published=cfg["n_routed_experts_published"],
        held_experts=(cfg["ep_rank"] * held, held),
        experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        max_len=min(cfg["max_position_embeddings"],
                    g["pages_per_seq"] * g["page_size"]),
        num_pages=g["num_pages"], page_size=g["page_size"],
        pages_per_seq=g["pages_per_seq"], dtype=g["dtype"],
        eos_id=g["eos_id"],
        seed=int(os.environ.get("PERF_GEN_SEED", "0")))
