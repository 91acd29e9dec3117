#!/usr/bin/env python3
"""Are the paged skeleton's programs what they were?  Dump the compiled
text (XLA:CPU, toy size) of `_decode_step`, `_verify_step`,
`_prefill_chunk` and `_prefill_bucket` for `Gpt2Block`, `OlmoeBlock`,
`ExaoneMoeBlock` and `OlmoHybridBlock` (the step and the bucket alone:
a model over state entries refuses the other two) and, since PR 64, for
Granite, Ling, LFM2, MiMo, Kanana, GLM-5 and Phi-4-mini-flash at their
own tests' toy sizes, with the metadata
dropped (op_name / source lines, and the file and function tables at
the head of the text), one file a program:

    JAX_PLATFORMS=cpu PYTHONPATH=<tree A> python scripts/program_identity.py /tmp/a
    JAX_PLATFORMS=cpu PYTHONPATH=<tree B> python scripts/program_identity.py /tmp/b
    diff -r /tmp/a /tmp/b        # empty: the same programs

Run it from this file in both cases (PYTHONPATH picks the tree), in
processes with the same XLA flags: a refactor that moves code between
functions changes only what is dropped here.  It cannot see the TPU
lowering of a Pallas kernel; compare the kernels' jaxprs for that.
"""

import importlib
import os
import re
import sys

import numpy as np

import jax.numpy as jnp


def strip(text):
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    head, rest = text.split("\n", 1)
    if "StackFrames" in rest:      # FileNames .. StackFrames tables
        rest = rest[rest.index("StackFrames"):]
        rest = rest[rest.index("\n\n"):]
    return head + rest


def dump(out, name, model, dm):
    S, P = 4, model.pages_per_seq
    kw = dict(heads=model.heads, page_size=model.page_size,
              block=model.block, extra=model.extra_pools)
    pools = (model.params, model.k_pool, model.v_pool)
    tables, lens = np.zeros((S, P), np.int32), np.zeros((S,), np.int32)
    lowered = {
        "decode": dm._decode_step.lower(
            *pools, tables, lens, np.zeros((S,), np.int32), **kw),
        "bucket": dm._prefill_bucket.lower(
            *pools, np.zeros((64,), np.int32),
            model._prompt_rows(model.allocator.alloc(P), 64, 3),
            np.int32(3), heads=model.heads, block=model.block,
            extra=model.extra_pools),
    }
    if getattr(model, "supports_verify", True):
        for key, program, args in (
                ("verify", dm._verify_step,
                 (tables, lens, np.zeros((S, 3), np.int32))),
                ("chunk", dm._prefill_chunk,
                 (jnp.zeros((P,), jnp.int32), np.int32(8),
                  jnp.zeros((5,), jnp.int32)))):
            try:
                lowered[key] = program.lower(*pools, *args, **kw)
            except RuntimeError as refused:     # by name: no such program
                if not type(refused).__name__.startswith("Unsupported"):
                    raise
    for key, low in lowered.items():
        with open(os.path.join(out, f"{name}.{key}.txt"), "w") as f:
            f.write(strip(low.compile().as_text()))


def main(out):
    from paddle_tpu.decode import model as dm
    from paddle_tpu.models.exaone_moe import ExaoneMoeLM
    from paddle_tpu.models.olmo_hybrid import FULL, LINEAR, OlmoHybridLM
    from paddle_tpu.models.olmoe import OlmoeLM

    os.makedirs(out, exist_ok=True)
    dump(out, "gpt2", dm.TinyDecoderLM(max_len=64), dm)
    dump(out, "olmoe", OlmoeLM(
        vocab=96, d_model=32, num_heads=4, num_layers=2, num_experts=8,
        experts_per_tok=2, expert_width=16, max_len=64, num_pages=32,
        page_size=8, pages_per_seq=8, dtype="float32"), dm)
    dump(out, "exaone", ExaoneMoeLM(
        vocab=96, d_model=32, num_heads=4, num_kv_heads=2, head_dim=8,
        layer_types=("sliding_attention",) * 2 + ("full_attention",),
        mlp_layer_types=("dense", "sparse", "sparse"), sliding_window=8,
        dense_width=48, expert_width=16, num_experts_published=8,
        held_experts=(2, 4), experts_per_tok=2, max_len=64, num_pages=64,
        page_size=8, pages_per_seq=8, dtype="float32"), dm)
    dump(out, "olmo_hybrid", OlmoHybridLM(
        vocab=96, d_model=32, num_heads=4, head_dim=8,
        layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 2,
        intermediate_size=48, linear_num_key_heads=4,
        linear_num_value_heads=4, linear_key_head_dim=6,
        linear_value_head_dim=10, max_len=64, num_pages=40, page_size=8,
        pages_per_seq=8, state_entries=5, dtype="float32"), dm)
    # every other model of the skeleton, at its own tests' toy sizes
    # (this file's tree's: the sizes are data, the models PYTHONPATH's)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tests"))
    for name, module, cls, sizes, where in (
            ("granite", "granite_hybrid", "GraniteHybridLM", "_GRANITE",
             "hybrid_models"),
            ("ling", "ling_hybrid", "LingHybridLM", "SIZES",
             "test_ling_hybrid"),
            ("lfm2", "lfm2_moe", "Lfm2MoeLM", "SIZES", "test_lfm2_moe"),
            ("mimo", "mimo_v2", "MimoV2LM", "SIZES", "test_mimo_v2"),
            ("kanana", "kanana_mla", "KananaMlaLM", "SIZES",
             "test_kanana_mla"),
            ("glm", "glm_dsa", "GlmDsaLM", "SIZES", "test_glm_dsa"),
            ("phi4", "phi4_flash", "Phi4FlashLM", "SIZES",
             "test_phi4_flash")):
        model = getattr(importlib.import_module(
            f"paddle_tpu.models.{module}"), cls)
        dump(out, name, model(seed=3, **getattr(
            importlib.import_module(where), sizes)), dm)
    print("tree", os.path.dirname(os.path.dirname(dm.__file__)), "dumped",
          len(os.listdir(out)), "programs to", out)


if __name__ == "__main__":
    main(sys.argv[1])
