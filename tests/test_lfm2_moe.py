"""LFM2-MoE behind /generate (``paddle_tpu/models/lfm2_moe.py``): gated
short-conv layers whose whole per-sequence state is a conv tail in a
state entry (no state pool) beside the K/V pages of RoPE attention
layers, dense then sigmoid-routed feed-forwards, and a long prompt in
chunks over the entry.  CPU, float32, toy widths that keep the ratios
(heads of 64 on fewer K/V heads, conv channels of whole lanes, a period
of four); the plain reference is ``perf/reference/lfm2_moe_block.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import pallas as pk
from paddle_tpu.decode import attention as A
from paddle_tpu.decode import state_entry as se
from paddle_tpu.decode.session import DecodeSession
from paddle_tpu.models import (granite_hybrid, ling_hybrid, lfm2_moe, moe,
                               olmo_hybrid, phi4_flash)
from paddle_tpu.models.lfm2_moe import ATTENTION, CONV, PERIOD
from paddle_tpu.observability import metrics
from paddle_tpu.pallas import conv_step as cs
from perf.reference import lfm2_moe_block as ref

SIZES = dict(vocab=96, d_model=128, num_heads=4, num_kv_heads=2, head_dim=64,
             layer_types=PERIOD * 2, num_dense_layers=2,
             intermediate_size=96, moe_intermediate_size=128, num_experts=8,
             experts_per_tok=2, max_len=256, num_pages=90, page_size=4,
             pages_per_seq=40, state_entries=5, prefill_rows=32,
             chunk_rows=16, dtype="float32")


def make(seed=3, **over):
    return lfm2_moe.Lfm2MoeLM(seed=seed, **{**SIZES, **over})


@pytest.fixture(autouse=True, scope="module")
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    return make()


@pytest.fixture
def kernels():
    pk.enable(True, interpret=True)
    jax.clear_caches()          # the mode is no part of a program's key
    try:
        yield
    finally:
        pk.enable("auto", interpret=False)
        jax.clear_caches()


def prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, SIZES["vocab"], n).tolist()


def reference(m, ids, **kw):
    b = m.block
    return ref.forward(
        m.params, jnp.asarray(ids, jnp.int32), layer_types=b.layer_types,
        num_heads=m.heads, head_dim=b.head_dim, top_k=b.top_k, scale=b.scale,
        route_eps=b.route_eps, eps=b.eps, theta=b.theta, **kw)


def through_the_cache(m, ids, tokens, slots=4, slot=2):
    """-> (the len(tokens) + 1 logits rows, the entry's tails)."""
    pages = m.allocator.alloc(m.context_pages(ids, len(tokens)))
    try:
        ctx, _, last = m.prefill(ids, pages)
        rows = [np.asarray(last)]
        tables = np.zeros((slots, m.pages_per_seq), np.int32)
        tables[slot] = m.pool_table(pages)
        lens = np.zeros((slots,), np.int32)
        lens[slot] = ctx
        for tok in tokens:
            step = np.full((slots, 1), m.bos_id, np.int64)
            step[slot, 0] = tok
            logits, _ = m.decode(step, [], tables, lens)
            lens[slot] += 1
            rows.append(np.asarray(logits[slot]))
        tails = np.asarray(m.conv_pool[:, m.allocator.entry_of(pages)])
    finally:
        m.allocator.free(pages)
    return np.stack(rows), tails.reshape(m.linear_layers, -1, m.d)


# -- the block against the reference ------------------------------------------


def test_dense_forward_is_the_reference(model):
    ids = prompt(29, 1)
    logits, _, _ = model._forward(jnp.asarray(ids, jnp.int32))
    want, masks = reference(model, ids)
    assert ref.rel_rms(logits, want) < 2e-6
    assert masks.shape == (6, 29, 8) and int(masks.sum()) == 6 * 29 * 2


@pytest.mark.parametrize("n", [5, 20, 32])
def test_prefill_then_steps_through_pages_and_entries_match_the_reference(
        model, n):
    ids, tokens = prompt(n, n), prompt(6, 100 + n)
    got, tails = through_the_cache(model, ids, tokens)
    want, _, want_tails = reference(
        model, ids + tokens, rows=list(range(n - 1, n + 6)), tails=True)
    assert max(ref.rel_rms(g, w) for g, w in zip(got, want)) < 1e-5
    assert ref.rel_rms(tails, want_tails) < 5e-6


def test_the_steps_by_the_kernels_are_the_xla_paths_steps(kernels):
    """``conv_step`` told to apply no activation and the grouped walk on
    the packed pages, interpreted, give the steps the gathered paths
    give."""
    m = make()
    ids, tokens = prompt(21, 7), prompt(5, 8)
    before = metrics.REGISTRY.get("pallas_dispatch_total").value(
        kernel="conv_step", path="interpret")
    got, tails = through_the_cache(m, ids, tokens)
    assert metrics.REGISTRY.get("pallas_dispatch_total").value(
        kernel="conv_step", path="interpret") > before
    want, _, want_tails = reference(
        m, ids + tokens, rows=list(range(20, 26)), tails=True)
    assert max(ref.rel_rms(g, w) for g, w in zip(got, want)) < 1e-5
    assert ref.rel_rms(tails, want_tails) < 5e-6


# -- a long prompt: the top bucket, then chunks over the entry ----------------


@pytest.mark.parametrize("n", [33, 45, 48, 64, 71])
def test_a_prompt_in_bucket_and_chunks_is_the_prompt_whole(model, n):
    """Past the 32-row top bucket a prompt runs as that bucket and then
    chunks of 16 (``n`` ends on a chunk's first row, inside one, on a
    chunk's end, two whole chunks on, and inside a third): every row's
    logits, through the shorter prompts that end on it, and the final
    entry are those of the prompt whole."""
    ids = prompt(n, 30 + n)
    assert model.prefill_bucket(n) == 32 + 16 * -(-(n - 32) // 16)
    want, _, want_tails = reference(model, ids, tails=True)
    ends = sorted({33, 34, 40, 47, 48, 49, 63, 64, 65, n} & set(
        range(33, n + 1)))
    for end in ends:
        got, tails = through_the_cache(model, ids[:end], [])
        assert ref.rel_rms(got[0], want[end - 1]) < 1e-5, end
    assert ref.rel_rms(tails, want_tails) < 5e-6
    # and the steps go on from the chunks' pages and entry
    tokens = prompt(3, n)
    got, tails = through_the_cache(model, ids, tokens)
    want, _, want_tails = reference(
        model, ids + tokens, rows=list(range(n - 1, n + 3)), tails=True)
    assert max(ref.rel_rms(g, w) for g, w in zip(got, want)) < 1e-5
    assert ref.rel_rms(tails, want_tails) < 5e-6


def test_a_chunk_from_an_empty_tail_is_what_the_ablation_shows(model):
    """The rows a carried tail moves are the chunk's first two; the
    reference's ``tail_zero_at_chunk`` moves those and the system's
    chunk does not read like it."""
    ids = prompt(36, 9)
    want = reference(model, ids, rows=[32, 33, 34, 35])[0]
    cut = reference(model, ids, rows=[32, 33, 34, 35],
                    ablate="tail_zero_at_chunk", chunk_at=32)[0]
    got = np.stack([through_the_cache(model, ids[:33 + j], [])[0][0]
                    for j in range(4)])
    assert ref.rel_rms(got, want) < 1e-5
    assert ref.rel_rms(cut[:2], want[:2]) > 0.05


def test_the_chunk_loop_counts_its_rows_pairs_and_spans(model):
    from paddle_tpu.observability import events

    rows = metrics.REGISTRY.get("decode_prefill_chunk_rows_total")
    pairs = metrics.REGISTRY.get("decode_prefill_chunk_pairs_total")
    r0, p0 = rows.value(over="state"), pairs.value(over="state")
    with events.recording() as ring:
        through_the_cache(model, prompt(53, 2), [])
        spans = [e for e in ring.events()
                 if e["name"] == "decode.prefill_chunk"]
    assert rows.value(over="state") - r0 == 21
    # 16 rows over 32, then 5 over 48, each with its own causal part
    assert pairs.value(over="state") - p0 == (16 * 32 + 16 * 17 // 2
                                              + 5 * 48 + 5 * 6 // 2)
    assert [(s["args"]["done"], s["args"]["rows"], s["args"]["bucket"])
            for s in spans] == [(32, 16, 16), (48, 5, 16)]


def test_prompt_chunk_attention_by_the_flash_kernel_is_plain_attention(
        kernels):
    """At a shape the flash forward takes (interpreted): a chunk of 128
    rows, 4 query heads on 2 K/V heads of 64, after 256 cached rows."""
    rng = np.random.RandomState(0)
    C, done, H, KV, D = 128, 256, 4, 2, 64
    q = jnp.asarray(rng.randn(C, H, D), jnp.float32)
    k, v = (jnp.asarray(rng.randn(C, KV, D), jnp.float32) for _ in range(2))
    kr, vr = (jnp.asarray(rng.randn(KV, done, D), jnp.float32)
              for _ in range(2))
    before = metrics.REGISTRY.get("pallas_dispatch_total").value(
        kernel="prefill_flash_attention", path="interpret")
    got = A.prompt_chunk_attention(q, k, v, kr, vr)
    assert metrics.REGISTRY.get("pallas_dispatch_total").value(
        kernel="prefill_flash_attention", path="interpret") == before + 2
    keys = jnp.concatenate([kr, jnp.moveaxis(k, 1, 0)], axis=1)
    vals = jnp.concatenate([vr, jnp.moveaxis(v, 1, 0)], axis=1)
    s = jnp.einsum("thd,hsd->hts", q, jnp.repeat(keys, H // KV, 0)) / 8.0
    seen = jnp.arange(C)[:, None] + done >= jnp.arange(done + C)[None, :]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    want = jnp.einsum("hts,hsd->thd", p, jnp.repeat(vals, H // KV, 0))
    assert ref.rel_rms(got, want) < 1e-5


# -- the shared pieces this model changed -------------------------------------


@pytest.mark.parametrize("activation", [cs.SILU, None])
@pytest.mark.parametrize("bias", [False, True])
def test_conv_step_with_and_without_activation_is_step_conv(activation,
                                                            bias):
    rng = np.random.RandomState(4)
    taps, C, E, S = 3, 256, 7, 5
    pool = jnp.asarray(rng.randn(E, *se.tail_shape(taps, C)), jnp.float32)
    at = jnp.asarray([3, 0, 6, 1, 0], jnp.int32)
    row = jnp.asarray(rng.randn(S, C), jnp.float32)
    w = jnp.asarray(rng.randn(taps, C), jnp.float32)
    b = jnp.asarray(rng.randn(C), jnp.float32) if bias else None
    want, kept = se.step_conv(pool[at].reshape(S, taps - 1, C), row, w, b,
                              activation)
    got, new = cs.conv_step(pool, at, row, w, b, activation=activation,
                            interpret=True)
    assert np.allclose(got, want, atol=1e-6)
    # slots 1 and 4 share the null entry: the later one's write stands
    for s in (0, 2, 3, 4):
        assert np.array_equal(new[at[s]].reshape(taps - 1, C), kept[s])
    acc = jnp.sum(jnp.concatenate(
        [pool[at].reshape(S, taps - 1, C), row[:, None]], 1) * w, axis=1)
    acc = acc if b is None else acc + b
    assert np.allclose(
        want, jax.nn.silu(acc) if activation else acc, atol=1e-6)


def test_the_default_conv_paths_numbers_are_todays_to_the_bit():
    """The four accepted callers pass no activation: ``step_conv`` and
    the kernel give ``silu`` of the sum as they did (the kernel named
    or not, bit for bit)."""
    rng = np.random.RandomState(5)
    kept = jnp.asarray(rng.randn(4, 3, 128), jnp.float32)
    row = jnp.asarray(rng.randn(4, 128), jnp.float32)
    w, b = (jnp.asarray(rng.randn(*s), jnp.float32)
            for s in ((4, 128), (128,)))
    rows = jnp.concatenate([kept, row[:, None]], axis=1)
    old = jax.nn.silu(jnp.sum(rows * w, axis=1) + b)
    assert np.array_equal(se.step_conv(kept, row, w, b)[0], old)
    pool = kept.reshape(4, 3, 128)
    got, _ = cs.conv_step(pool, jnp.arange(4, dtype=jnp.int32), row, w, b,
                          interpret=True)
    # the kernel's default is SiLU, named or not (its numbers differ
    # from the reduction's by the order of the sum, as they did)
    named, _ = cs.conv_step(pool, jnp.arange(4, dtype=jnp.int32), row, w, b,
                            activation=cs.SILU, interpret=True)
    assert np.array_equal(got, named) and np.allclose(got, old, atol=1e-6)
    with pytest.raises(ValueError, match="activation"):
        se.step_conv(kept, row, w, b, "gelu")
    # a prompt's conv without the rows of an earlier chunk: as it was
    z = jnp.asarray(rng.randn(9, 128), jnp.float32)
    zp = jnp.concatenate([jnp.zeros((3, 128)), z])
    assert np.array_equal(se.causal_conv(z, w),
                          sum(zp[j:j + 9] * w[j] for j in range(4)))
    assert np.array_equal(se.conv_tail(z, 4, 5), z[2:5])


def test_the_router_rule_with_its_epsilon():
    logits = jnp.asarray([[2.0, -1.0, 0.5, 0.5, -3.0]])
    bias = jnp.asarray([0.0, 3.0, 0.0, 0.0, 0.0])
    rank, weigh, weights_of = moe.sigmoid_scores(bias, 1.0, 1e-6)(logits)
    s = jax.nn.sigmoid(logits)
    assert np.allclose(rank, s + bias) and np.array_equal(weigh, s)
    chosen = jnp.asarray([[0.2, 0.3]])
    assert np.allclose(weights_of(chosen), chosen / (0.5 + 1e-6), rtol=1e-7)
    # epsilon 0, the accepted callers' default: the quotient as it was
    plain = moe.sigmoid_scores(bias, 2.5)(logits)[2](chosen)
    assert np.array_equal(plain, 2.5 * chosen / jnp.sum(chosen, -1,
                                                        keepdims=True))
    # ranked by s + bias, a tie to the lower index; weighed by s alone
    w, idx = moe.route(jnp.ones((1, 5)), jnp.diag(logits[0]), 2,
                       moe.sigmoid_scores(bias, 1.0, 1e-6))
    assert idx.tolist() == [[1, 0]]
    picked = s[0, jnp.asarray([1, 0])]
    assert np.allclose(w[0], picked / (picked.sum() + 1e-6))


def test_two_leading_dense_layers_then_routed_ones(model):
    kinds = ["wr" in lp for lp in model.params["layers"]]
    assert kinds == [False, False] + [True] * 6
    assert model.params["layers"][0]["w_gate"].shape == (128, 96)
    assert model.params["layers"][2]["w_gate"].shape == (8, 128, 128)
    assert model.block.layer_types.count(CONV) == 6
    assert [i for i, t in enumerate(model.block.layer_types)
            if t == ATTENTION] == [2, 6]


# -- the cache: what exists is counted ----------------------------------------


def test_there_is_no_state_pool_and_the_gauges_count_what_exists(model):
    assert model.state_pool.shape == (6, 1)         # a placeholder
    assert model.conv_pool.shape == (6, 5, 2, 128)
    assert model.entry_bytes() == 6 * 2 * 128 * 4
    assert model.cache_rows([10, 20]) == {"full": 30 * 2, "state": 2 * 6}
    assert model.cache_bytes([10, 20]) == {
        "full": 30 * 2 * 2 * 2 * 64 * 4, "state": 2 * model.entry_bytes()}


def test_a_sequences_state_is_nine_tails_of_8_kb_at_the_published_widths():
    """The arithmetic of ``decode_cache_bytes{kind="state"}`` at the
    published widths, from shapes alone (nothing allocated)."""
    shape = se.tail_shape(3, 2048)
    assert shape == (32, 128)
    m = make()
    m.extra_pools = (jax.ShapeDtypeStruct((9, 1), jnp.float32),
                     jax.ShapeDtypeStruct((9, 65) + shape, jnp.bfloat16))
    assert m.entry_bytes() == 9 * 8192
    assert m.cache_bytes([7281])["state"] == 9 * 8192


def test_bucket_padding_leaves_the_tail_at_the_last_real_row(model):
    ids = prompt(19, 3)
    _, tails = through_the_cache(model, ids, [])
    _, _, want = reference(model, ids, tails=True)
    assert ref.rel_rms(tails, want) < 5e-6
    _, short = through_the_cache(model, ids[:1], [])
    assert np.array_equal(short[:, 0], np.zeros_like(short[:, 0]))


# -- what is refused, by name -------------------------------------------------


def test_what_a_tail_at_an_earlier_row_would_need_is_refused_by_name(model):
    session = DecodeSession(model, max_slots=2, prefix_cache=object(),
                            spec_draft=object())
    assert session.prefix_cache is None and session._spec_draft is None
    pages = model.allocator.alloc(4)
    try:
        with pytest.raises(se.UnsupportedOverState, match="cached"):
            model.prefill([3] * 12, pages, cached_len=8)
    finally:
        model.allocator.free(pages)
    with pytest.raises(se.UnsupportedOverState, match="fork"):
        model.copy_page(1, 2)
    with pytest.raises(se.UnsupportedOverState, match="verify"):
        model.verify_chunk(np.zeros((2, 3), np.int64), [], None, None)
    with pytest.raises(se.UnsupportedOverState, match="state between"):
        model.block.layer(0).mixer(None, jnp.zeros((2, 3, 128)), None, None,
                                   0, None, 4)
    with pytest.raises(ValueError, match="outside 1..160"):
        model.prefill_bucket(161)


@pytest.mark.parametrize("block", [
    olmo_hybrid.OlmoHybridBlock, granite_hybrid.GraniteHybridBlock,
    phi4_flash.Phi4FlashBlock, ling_hybrid.LingHybridBlock],
    ids=lambda c: c.__name__)
def test_a_chunk_over_the_other_hybrids_state_is_refused_by_name(block):
    """The hook is the base's; one layer kind fills it.  The other four
    blocks refuse a chunk over their state entry, and over their pages,
    saying what is missing."""
    b = block()
    lb = b.layer(b.layer_types.index(block.recurrent_kind))
    chunk = se.PromptChunk(32, jnp.int32(4), None, None)
    with pytest.raises(se.UnsupportedOverState,
                       match="recurrent_chunk") as e:
        lb.chunk_mixer(None, None, None, (None,) * 4, 0, chunk, 4)
    assert block.recurrent_kind in str(e.value)
    other = next(i for i, t in enumerate(b.layer_types)
                 if t != block.recurrent_kind)
    with pytest.raises(se.UnsupportedOverState, match="page_chunk"):
        b.layer(other).chunk_mixer(None, None, None, (None,) * 4, other,
                                   chunk, 4)


# -- behind the session -------------------------------------------------------


def test_session_serves_short_and_chunked_prompts(model):
    from paddle_tpu.decode.session import DecodeRequest

    session = DecodeSession(model, max_slots=4)
    prompts = [prompt(n, 60 + n) for n in (6, 40, 71)]
    reqs = [session.submit(DecodeRequest(p, max_new_tokens=3))
            for p in prompts]
    for _ in range(40):
        if all(r.done for r in reqs):
            break
        session.step()
    for p, r in zip(prompts, reqs):
        want = []
        for _ in range(3):
            logits = reference(model, p + want, rows=[len(p + want) - 1])[0]
            want.append(int(np.argmax(logits[0])))
        assert list(r.tokens) == want
    assert model.allocator.pages_in_use == 0
    assert model.allocator.free_entries == 4


def test_named_scopes_place_the_layers(model):
    cache = model._cache()
    from paddle_tpu.decode import model as dm

    step = dm._decode_step.trace(
        model.params, *cache[:2], np.zeros((4, 41), np.int32),
        np.zeros((4,), np.int32), np.zeros((4,), np.int32), heads=4,
        page_size=4, block=model.block, extra=cache[2:]).lower().as_text(
            debug_info=True)
    for scope in ("blk_mixer/short_conv/short_conv_step",
                  "blk_mixer/attn_full", "blk_mlp/moe_experts"):
        assert scope in step, scope
    chunk = se._prefill_state_chunk.trace(
        model.params, *cache[:2], np.zeros((41,), np.int32),
        np.zeros((16,), np.int32), np.int32(3), heads=4, page_size=4,
        block=model.block, done=32, extra=cache[2:]).lower().as_text(
            debug_info=True)
    for scope in ("blk_mixer/short_conv/short_conv_scan",
                  "blk_mixer/attn_full/attn_chunk", "blk_mlp/moe_router",
                  "blk_head"):
        assert scope in chunk, scope
