"""`paddle serve --gen_config` script of the `ling-3.0-flash`
configuration, and the documented way to serve the model:
``make_decode_model()`` returns one chip's share of the layers stage 0
of a seven-stage pipeline holds of Ling-3.0-flash
(``paddle_tpu/models/ling_hybrid.py``: five Kimi-Delta-Attention layers,
the delta rule under a decay that is a vector over a head's 128 key
channels, a float32 state a sequence a layer; one latent-attention
layer, one 576-wide row a token on the pages; from the one cache
manager a latent page run and a state entry a sequence; the sigmoid
router over the published 512 experts under its group step, of which
128 are held beside the shared expert; 1/4 of the vocabulary; bfloat16
weights and latent rows) over the repo's paged decoder, at the
published widths.  **Random weights from a seed; loading a checkpoint
is not supported.**

    scripts/paddle serve \
        --gen_config=perf/configs/ling-3.0-flash.gen_config.py \
        --gen_slots=128 --gen_queue=256 --gen_max_tokens=2039

Sizes come from ``ling-3.0-flash.json`` beside this file.
``PERF_GEN_SEED`` seeds the weights (default 0); ``PERF_GEN_REHEARSE=1``
takes the file's toy ``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.models.ling_hybrid import LingHybridLM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "ling-3.0-flash.json")) as f:
        cfg = json.load(f)
    if os.environ.get("PERF_GEN_REHEARSE") == "1":
        cfg = {**cfg, **cfg["rehearse"],
               "generate": {**cfg["generate"],
                            **cfg["rehearse"].get("generate", {})}}
    g = cfg["generate"]
    held = cfg["num_experts"]               # this rank's contiguous range
    return LingHybridLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_layers=cfg["num_hidden_layers"],    # the first of the published
        layer_group_size=cfg["layer_group_size"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        linear_num_heads=cfg["linear_num_key_heads"],
        linear_head_dim=cfg["linear_key_head_dim"],
        short_conv_kernel_size=cfg["short_conv_kernel_size"],
        kda_lower_bound=cfg["kda_lower_bound"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=(cfg["num_shared_experts"]
                      * cfg["moe_shared_expert_intermediate_size"]),
        num_experts_published=cfg["num_experts_published"],
        held_experts=(cfg["deployment_ep_rank"] * held, held),
        experts_per_tok=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        expert_swiglu_limits=cfg["expert_swiglu_limit_list"],
        shared_swiglu_limits=cfg["share_expert_swiglu_limit_list"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        max_len=min(cfg["max_position_embeddings"],
                    g["pages_per_seq"] * g["page_size"]),
        num_pages=g["num_pages"], page_size=g["page_size"],
        pages_per_seq=g["pages_per_seq"],
        state_entries=g["state_entries"], dtype=g["dtype"],
        eos_id=g["eos_id"],
        seed=int(os.environ.get("PERF_GEN_SEED", "0")))
