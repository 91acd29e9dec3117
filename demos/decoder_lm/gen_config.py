"""`paddle serve --gen_config` script for the paged decoder LM: defines
``make_decode_model()``, the branch of ``paddle serve`` that mounts
POST /generate over ``paddle_tpu/decode/model.py TinyDecoderLM`` — the
self-attention consumer of the ragged paged-attention kernel (prefix
caching and speculative decoding work on this branch; the seq2seq
``make_generator()`` branch in demos/seq2seq/gen_config.py pages a
static cross-attention context instead).

Weights are random, from the seed below: the model proves the engine's
path and its kernels, not a trained LM.

    scripts/paddle serve --gen_config=demos/decoder_lm/gen_config.py \
        --gen_slots=4 --gen_max_tokens=16
"""

from paddle_tpu.decode.model import TinyDecoderLM


def make_decode_model():
    return TinyDecoderLM(vocab=64, d_model=32, num_heads=4, num_layers=2,
                         max_len=64, num_pages=64, page_size=8,
                         pages_per_seq=8, seed=0)
