"""`paddle serve --gen_config` script of the `glm-5` configuration, and
the documented way to serve the model: ``make_decode_model()`` returns
one chip's share of GLM-5 (``paddle_tpu/models/glm_dsa.py``: latent
attention, 64 heads on ONE stored row a token a layer, whose every query
row reads only the 2,048 cached rows a learned indexer chooses: an
index-key row of 128 numbers beside every latent row on the same pages,
the selection inside the decode step, prompts over 8,192 rows prefilled
in chunks; the sigmoid router over the published 256 experts of which 16
are held beside the shared expert, 1/8 of the vocabulary, bfloat16
weights, latent rows and index rows) over the repo's paged decoder, at
the published widths, one leading dense layer and the 4 routed layers
that follow it.  **Random weights from a seed; loading a checkpoint is
not supported.**

    scripts/paddle serve \
        --gen_config=perf/configs/glm-5.gen_config.py \
        --gen_slots=32 --gen_max_tokens=1021

Sizes come from ``glm-5.json`` beside this file.  ``PERF_GEN_SEED``
seeds the weights (default 0); ``PERF_GEN_REHEARSE=1`` takes the file's
toy ``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.models.glm_dsa import GlmDsaLM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "glm-5.json")) as f:
        cfg = json.load(f)
    if os.environ.get("PERF_GEN_REHEARSE") == "1":
        cfg = {**cfg, **cfg["rehearse"],
               "generate": {**cfg["generate"],
                            **cfg["rehearse"].get("generate", {})}}
    g = cfg["generate"]
    held = cfg["n_routed_experts"]          # this rank's contiguous range
    return GlmDsaLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_layers=cfg["num_hidden_layers"],    # the first of the published
        first_k_dense_replace=cfg["leading_dense_layers"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        index_rope_dim=cfg["qk_rope_head_dim"],
        index_topk=cfg["index_topk"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["n_shared_experts"],
        num_experts_published=cfg["n_routed_experts_published"],
        held_experts=(cfg["deployment_ep_rank"] * held, held),
        experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        max_len=min(cfg["max_position_embeddings"],
                    g["pages_per_seq"] * g["page_size"]),
        num_pages=g["num_pages"], page_size=g["page_size"],
        pages_per_seq=g["pages_per_seq"], prefill_rows=g["prefill_rows"],
        chunk_rows=g["chunk_rows"], dtype=g["dtype"], eos_id=g["eos_id"],
        seed=int(os.environ.get("PERF_GEN_SEED", "0")))
