"""`paddle serve --gen_config` script of the `nemotron-3-nano-30b-a3b`
configuration, and the documented way to serve the model:
``make_decode_model()`` returns one chip's share of Nemotron-3-Nano-
30B-A3B (``paddle_tpu/models/nemotron_h.py``: all 52 published layers,
each ONE part: 23 Mamba-2 layers in 8 groups whose states live in a
state entry a sequence, 6 attention layers of 32 query heads on 2 K/V
heads of 128 without rotation on a page run, 23 layers of two-matrix
relu^2 experts, 16 held of the published 128 beside a shared one; 1/8 of
the vocabulary; bfloat16 weights and pages, float32 states) over the
repo's paged decoder, at the published widths.  **Random weights from a
seed; loading a checkpoint is not supported.**

    scripts/paddle serve \
        --gen_config=perf/configs/nemotron-3-nano-30b-a3b.gen_config.py \
        --gen_slots=32 --gen_max_tokens=2048

Sizes come from ``nemotron-3-nano-30b-a3b.json`` beside this file.
``PERF_GEN_SEED`` seeds the weights (default 0); ``PERF_GEN_REHEARSE=1``
takes the file's toy ``rehearse`` sizes (CPU control-flow check).
"""

import json
import os

from paddle_tpu.models.nemotron_h import NemotronHLM

_HERE = os.path.dirname(os.path.abspath(__file__))


def make_decode_model():
    with open(os.path.join(_HERE, "nemotron-3-nano-30b-a3b.json")) as f:
        cfg = json.load(f)
    if os.environ.get("PERF_GEN_REHEARSE") == "1":
        cfg = {**cfg, **cfg["rehearse"],
               "generate": {**cfg["generate"],
                            **cfg["rehearse"].get("generate", {})}}
    g = cfg["generate"]
    held = cfg["n_routed_experts"]          # this rank's contiguous range
    return NemotronHLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        pattern=cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_shared_expert_intermediate_size"],
        num_experts_published=cfg["n_routed_experts_published"],
        held_experts=(cfg["ep_rank"] * held, held),
        experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        # the longest prompt: the top bucket (a sequence holds
        # pages_per_seq x page_size rows, the prompt and its answer)
        max_len=min(cfg["max_position_embeddings"], g["prefill_rows"]),
        num_pages=g["num_pages"], page_size=g["page_size"],
        pages_per_seq=g["pages_per_seq"], state_entries=g["state_entries"],
        dtype=g["dtype"], eos_id=g["eos_id"],
        seed=int(os.environ.get("PERF_GEN_SEED", "0")))
