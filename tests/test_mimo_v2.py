"""MiMo-V2.5's block over the paged skeleton
(``paddle_tpu/models/mimo_v2.py``) at small sizes, float32, on the CPU:
prefill and decode through both caches (the full layers' page run, the
window layers' ring entry) against the plain reference
(``perf/reference/mimo_v2_block.py``); a prompt in chunks over its
rings against the prompt whole; the expert shares against the uncut
layer; the sink, the partial rotation and keys wider than values
through each attention form; the cache manager's two resources; what
is refused, by name.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import pallas as pk
from paddle_tpu.decode import attention as pa
from paddle_tpu.decode import model as dm
from paddle_tpu.decode import state_entry as se
from paddle_tpu.decode.paged_kv import PoolExhausted
from paddle_tpu.decode.session import DecodeRequest, DecodeSession
from paddle_tpu.models import mimo_v2
from paddle_tpu.observability import metrics
from perf.reference import mimo_v2_block as ref

# pages of 8 rows = the window; rings of 16; a top bucket of 64 rows,
# chunks of 16; heads of 24 (8 rotated) on values of 16
SIZES = dict(vocab=96, d_model=32, num_heads=8, num_kv_heads=2,
             swa_num_kv_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
             sliding_window=8, dense_width=64, expert_width=16,
             num_experts_published=16, held_experts=(0, 4),
             experts_per_tok=2, max_len=256, num_pages=64, page_size=8,
             pages_per_seq=32, ring_entries=5, prefill_rows=64,
             chunk_rows=16, dtype="float32")


def make(seed=3, **over):
    return mimo_v2.MimoV2LM(seed=seed, **{**SIZES, **over})


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


def prompt(n, seed):
    return np.random.RandomState(seed).randint(2, SIZES["vocab"], n).tolist()


def reference(model, ids, rows=None, ablate=None, held=None):
    b = model.block
    return ref.forward(
        model.params, jnp.asarray(ids, jnp.int32), layer_types=b.layer_types,
        num_heads=model.heads, kv_heads=b.kv_heads,
        window_kv_heads=b.window_kv_heads, head_dim=b.head_dim,
        value_dim=b.value_dim, rotary=b.rotary, window=b.window,
        top_k=b.top_k, scale=b.scale, held=held or b.held, eps=b.eps,
        theta=b.theta, window_theta=b.window_theta,
        value_scale=b.value_scale, ablate=ablate, rows=rows)


def through_the_caches(model, ids, n, slots=4, slot=2):
    """Prefill ``ids[:n]``, then the rest teacher-forced a decode step
    each: the len(ids) - n + 1 logits rows."""
    p, toks = ids[:n], ids[n:]
    pages = model.allocator.alloc(model.context_pages(p, len(toks)))
    try:
        ctx, _, last = model.prefill(p, pages)
        rows = [np.asarray(last)]
        tables = np.zeros((slots, model.pages_per_seq), np.int32)
        tables[slot] = model.pool_table(pages)
        lens = np.zeros((slots,), np.int32)
        lens[slot] = ctx
        for t in toks:
            step = np.full((slots, 1), 1, np.int64)
            step[slot, 0] = t
            logits, _ = model.decode(step, [], tables, lens)
            lens[slot] += 1
            rows.append(np.asarray(logits[slot]))
    finally:
        model.allocator.free(pages)
    return np.stack(rows)


# -- the model against the reference ------------------------------------------


def test_dense_forward_is_the_reference(model):
    ids = prompt(45, 1)
    got = model._forward(jnp.asarray(ids, jnp.int32))[0]
    want, masks = reference(model, ids)
    assert masks.shape == (6, 45, 16)
    np.testing.assert_allclose(got, want, atol=2e-6)


# under a window; over a ring's wrap (16 rows); the top bucket; the
# bucket and chunks of 16 over the rings (one, several, a partial last)
@pytest.mark.parametrize("n", [5, 20, 64, 70, 100, 131])
def test_prefill_then_steps_through_both_caches_match_the_reference(model,
                                                                    n):
    ids = prompt(n + 6, 10 + n)
    got = through_the_caches(model, ids, n)
    want, _ = reference(model, ids, rows=list(range(n - 1, n + 6)))
    np.testing.assert_allclose(got, want, atol=3e-6)


def test_steps_through_the_walk_kernel_match_the_reference(model, kernels):
    """The decode step's full layers by the grouped walk, interpreted
    (K pages of 128 lanes for keys of 24, V pages of 16)."""
    dm._decode_step.clear_cache()
    before = pk._M_DISPATCH.value(kernel="ragged_paged_attention_gqa",
                                  path="interpret")
    ids = prompt(26, 7)
    got = through_the_caches(model, ids, 20)
    dm._decode_step.clear_cache()
    assert pk._M_DISPATCH.value(kernel="ragged_paged_attention_gqa",
                                path="interpret") > before
    want, _ = reference(model, ids, rows=list(range(19, 26)))
    np.testing.assert_allclose(got, want, atol=3e-6)


@pytest.mark.parametrize("n", [65, 80, 97, 140])
def test_a_prompt_in_chunks_over_its_rings_is_the_prompt_whole(model, n):
    """The top bucket and chunks of 16 leave what ONE bucket of the
    whole prompt leaves: the last row's logits and the tokens' that
    follow through both caches."""
    whole = make(prefill_rows=256, chunk_rows=256)
    assert not whole.prompt_chunks(n) and model.prompt_chunks(n)
    ids = prompt(n + 4, n)
    np.testing.assert_allclose(through_the_caches(model, ids, n),
                               through_the_caches(whole, ids, n), atol=3e-6)


@pytest.mark.parametrize("ablate,least", [
    ("sink", 0.1), ("sink_on_full", 0.05), ("window", 0.1),
    ("partial_rope", 0.1), ("theta", 0.05), ("v_scale", 0.1), ("gqa", 0.1),
    ("kv_heads_swapped", 0.1), ("sigmoid", 5e-3), ("no_renorm", 3e-4),
    ("bias_in_weights", 5e-5), ("fp8", 0.02)])
def test_each_ablation_of_the_reference_moves_the_logits(model, ablate,
                                                         least):
    """What the chip's comparison counts on: each piece of the block
    shows in the logits of a prompt longer than a window (float32 sees
    even the selection bias used as a weight, which the bfloat16 noise
    floor hides at the served widths)."""
    ids = prompt(60, 5)
    got = model._forward(jnp.asarray(ids, jnp.int32))[0]
    wrong, _ = reference(model, ids, ablate=ablate)
    assert ref.rel_rms(got, wrong) > least
    assert ablate in ref.ABLATIONS


def test_the_expert_shares_add_up_to_the_uncut_layer(model):
    """Four chips' held ranges of four experts give, added, what the
    uncut layer gives: there is no shared expert to count once."""
    block = model.block
    lp = model.params["layers"][1]
    rng = np.random.RandomState(0)
    E, d, f = 16, SIZES["d_model"], SIZES["expert_width"]
    full = {**lp,
            "w_gate": jnp.asarray(rng.randn(E, d, f) * 0.3, jnp.float32),
            "w_up": jnp.asarray(rng.randn(E, d, f) * 0.3, jnp.float32),
            "w_down": jnp.asarray(rng.randn(E, f, d) * 0.3, jnp.float32)}
    x = jnp.asarray(rng.randn(12, d), jnp.float32)
    total = jnp.zeros_like(x)
    for rank in range(4):
        share = {**full, **{k: full[k][rank * 4:rank * 4 + 4]
                            for k in ("w_gate", "w_up", "w_down")}}
        lb = mimo_v2.dataclasses.replace(block.layer(1), held=(rank * 4, 4))
        y, report = lb.mlp(share, x, None)
        assert int(report.sum()) == 12 * block.top_k
        total = total + (y - x)
    m = ref.rms_norm(x, full["w_post"], block.eps)
    want, mask = ref.feed_forward(full, m, top_k=block.top_k,
                                  scale=block.scale, held=(0, E), ablate=None)
    assert mask.shape == (12, E) and int(mask.sum()) == 12 * block.top_k
    np.testing.assert_allclose(total, want, atol=2e-5)


# -- the pieces ---------------------------------------------------------------


@pytest.mark.parametrize("shape", [(3, 7), (2, 4, 5, 9)])
def test_a_softmax_with_a_sink_is_a_softmax_over_one_more_zero_valued_key(
        shape):
    rng = np.random.RandomState(1)
    s = jnp.asarray(rng.randn(*shape) * 3, jnp.float32)
    sink = jnp.asarray(rng.randn(*shape[:-1]), jnp.float32)
    got = pa._softmax(s, sink)
    want = jax.nn.softmax(
        jnp.concatenate([s, sink[..., None]], axis=-1), axis=-1)[..., :-1]
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert np.all(np.asarray(got.sum(-1)) < 1.0)
    np.testing.assert_array_equal(pa._softmax(s), jax.nn.softmax(s, -1))
    # a row that sees no key: zeros under a sink, no NaN
    none = pa._softmax(jnp.full(shape, pa._NEG_INF), sink)
    np.testing.assert_array_equal(none, np.zeros(shape, np.float32))


def test_rotation_of_the_first_64_of_192_channels():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(9, 4, 192), jnp.float32)
    pos = jnp.arange(9, dtype=jnp.int32) + 1000
    cos, sin = mimo_v2.rope_angles(pos, 64, 1e4)
    got = mimo_v2.partial_rope(x, cos, sin, 64)
    np.testing.assert_allclose(got, ref.rope(x, pos, 1e4, 64), atol=2e-5)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    # channel i pairs with i + 32, at frequency theta^(-i / 32)
    ang = 1003 * 1e4 ** (-5 / 32)
    np.testing.assert_allclose(
        got[3, 1, 5], x[3, 1, 5] * np.cos(ang) - x[3, 1, 37] * np.sin(ang),
        atol=2e-5)


def _explicit_window(q, k, v, window, sink, scale, first=0):
    """Dense banded attention of q (rows ``first`` ..) over all of k, v,
    with the sink, in numpy."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    (T, Hq, _), Hkv = q.shape, k.shape[1]
    G, out = Hq // Hkv, np.zeros((T, Hq, v.shape[-1]))
    for t in range(T):
        lo = max(0, first + t - window + 1)
        for h in range(Hq):
            s = k[lo:first + t + 1, h // G] @ q[t, h] * scale
            e = np.exp(s - s.max())
            p = e / (e.sum() + np.exp(float(sink[h]) - s.max()))
            out[t, h] = p @ v[lo:first + t + 1, h // G]
    return out


@pytest.mark.parametrize("T", [32, 21])
def test_banded_prefill_with_a_sink_and_keys_wider_than_values(T):
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(T, 8, 24), jnp.float32)
    k = jnp.asarray(rng.randn(T, 4, 24), jnp.float32)
    v = jnp.asarray(rng.randn(T, 4, 16), jnp.float32)
    sink = jnp.asarray(rng.randn(8) + 1, jnp.float32)
    got = pa.banded_prefill_attention(q, k, v, 8, sink=sink, scale=0.3)
    assert got.shape == (T, 8, 16)
    np.testing.assert_allclose(
        got, _explicit_window(q, k, v, 8, sink, 0.3), atol=2e-5)


def test_a_banded_chunk_sees_the_rows_before_it():
    """``before``: a chunk's first block sees the ring's newest page."""
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(24, 8, 24), jnp.float32)
    k = jnp.asarray(rng.randn(32, 4, 24), jnp.float32)
    v = jnp.asarray(rng.randn(32, 4, 16), jnp.float32)
    sink = jnp.asarray(rng.randn(8), jnp.float32)
    got = pa.banded_prefill_attention(
        q, k[8:], v[8:], 8, sink=sink, scale=0.2, before=(k[:8], v[:8]))
    np.testing.assert_allclose(
        got, _explicit_window(q, k, v, 8, sink, 0.2, first=8), atol=2e-5)
    with pytest.raises(ValueError, match="whole blocks"):
        pa.banded_prefill_attention(q[:5], k[:5], v[:5], 8,
                                    before=(k[:8], v[:8]))


def test_the_ring_step_with_a_sink_is_the_explicit_window():
    """``ring_window_attention`` on a two-page ring that has wrapped,
    keys at stored lanes wider than the values."""
    rng = np.random.RandomState(5)
    pg, n = 8, 21                                   # positions 0..20 written
    k = jnp.asarray(rng.randn(n, 4, 32), jnp.float32)
    v = jnp.asarray(rng.randn(n, 4, 16), jnp.float32)
    q = jnp.asarray(rng.randn(1, 1, 8, 32), jnp.float32)
    sink = jnp.asarray(rng.randn(8) + 1, jnp.float32)
    at = np.arange(n) % (2 * pg)
    ring_k = jnp.zeros((2 * pg, 4, 32)).at[at].set(k)       # later wins
    ring_v = jnp.zeros((2 * pg, 4, 16)).at[at].set(v)
    got = pa.ring_window_attention(
        q, ring_k.reshape(1, 2, pg, 4, 32), ring_v.reshape(1, 2, pg, 4, 16),
        jnp.asarray([[n - 1]]), 8, pg, sink=sink, scale=0.25)
    want = _explicit_window(q[0], k, v, 8, sink, 0.25, first=n - 1)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("heads_major", [False, True])
def test_the_walk_on_keys_wider_than_values_is_its_reference(T, heads_major):
    """K pages of 256 lanes on V pages of 128 through the grouped walk,
    interpreted: the accumulator and the output are the values' wide."""
    rng = np.random.RandomState(6)
    S, N, pg, Hkv, Hq, P = 3, 9, 8, 2, 8, 4
    shape = (N, Hkv, pg) if heads_major else (N, pg, Hkv)
    k_pages = jnp.asarray(rng.randn(*shape, 256), jnp.float32)
    v_pages = jnp.asarray(rng.randn(*shape, 128), jnp.float32)
    q = jnp.asarray(rng.randn(S, T, Hq, 256), jnp.float32)
    tables = jnp.asarray(rng.randint(1, N, (S, P)), jnp.int32)
    lens = jnp.asarray([0, 13, 27], jnp.int32)
    got = pa.ragged_paged_attention_gqa(
        q, k_pages, v_pages, tables, lens, scale=0.07, interpret=True,
        heads_major=heads_major)
    want = pa.ragged_paged_attention_gqa_reference(
        q, k_pages, v_pages, tables, lens, scale=0.07,
        heads_major=heads_major)
    assert got.shape == (S, T, Hq, 128)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_ring_kernel_on_keys_wider_than_values_is_its_reference():
    """The window kernel's output is the values' wide too (heads-major
    pages, Phi's layout)."""
    rng = np.random.RandomState(8)
    S, N, pg, Hkv, Hq, R = 2, 7, 8, 2, 4, 2
    k_pages = jnp.asarray(rng.randn(N, Hkv, pg, 256), jnp.float32)
    v_pages = jnp.asarray(rng.randn(N, Hkv, pg, 128), jnp.float32)
    q = jnp.asarray(rng.randn(S, 1, Hq, 256), jnp.float32)
    rings = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.asarray([5, 19], jnp.int32)
    got = pa.ring_paged_attention(q, k_pages, v_pages, rings, lens, 8,
                                  interpret=True, heads_major=True)
    want = pa.ring_window_attention(
        q, jnp.swapaxes(k_pages[rings], 2, 3),
        jnp.swapaxes(v_pages[rings], 2, 3), lens[:, None], 8, pg)
    assert got.shape == (S, 1, Hq, 128)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("first,n", [(0, 5), (0, 8), (0, 13), (0, 40),
                                     (64, 3), (64, 16), (80, 9)])
def test_what_a_ring_keeps_of_a_run_of_rows(first, n):
    """Ring row ``p % 16`` holds position ``p`` for the newest 16
    positions at most; rows a run does not reach keep what they held."""
    at, reached = mimo_v2.ring_rows_of(first, jnp.int32(n), 64, 8)
    at, reached = np.asarray(at), np.asarray(reached)
    last = first + n - 1
    for r in range(16):
        # the newest position <= last on ring row r's page slot and offset
        page = last // 8 - (last // 8 - r // 8) % 2
        p = page * 8 + r % 8
        assert reached[r] == (p >= first)
        if reached[r]:
            assert at[r] == p - first
    # every real row of the newest window is kept where a step reads it
    for p in range(max(first, last - 7), last + 1):
        assert reached[p % 16] and at[p % 16] == p - first


# -- the two resources --------------------------------------------------------


def test_a_sequence_holds_a_page_run_and_one_ring_entry(model):
    alloc = model.allocator
    assert isinstance(alloc, mimo_v2.RingRunManager)
    assert model.pages_per_seq == model.full_pages + 1 == 33
    assert (model.full_layers, model.window_layers) == (2, 5)
    assert model.k_pool.shape == (2, 64, 8, 2, 128)      # keys of 24 at 128
    assert model.v_pool.shape == (2, 64, 8, 2, 16)
    assert [p.shape for p in model.extra_pools] == [
        (5, 5, 16, 4, 128), (5, 5, 16, 4, 16)]
    need = model.context_pages([2] * 20, 10)
    assert need == 4 + 1                        # 30 rows: 4 pages, an entry
    ids = alloc.alloc(need)
    table = model.pool_table(ids)
    assert list(table[:4]) == alloc.pages_of(ids) and not table[4:32].any()
    assert table[32] == alloc.entry_of(ids) > 0
    gauge = metrics.REGISTRY.get("decode_cache_resource")
    assert gauge.value(resource="run_pages", state="in_use") == 4
    assert gauge.value(resource="ring_entries", state="in_use") == 1
    assert gauge.value(resource="ring_entries", state="free") == 3
    alloc.free(ids)
    assert alloc.pages_in_use == 0 and alloc.free_entries == 4
    assert gauge.value(resource="run_pages", state="free") == 63


@pytest.mark.parametrize("short", ["pages", "entries"])
def test_admission_is_refused_when_either_resource_runs_out(short):
    """All or nothing: with too few pages, or no ring entry, nothing is
    taken and the request waits; both come back together."""
    m = make(num_pages=8 if short == "pages" else 64, ring_entries=3)
    alloc = m.allocator
    held = [alloc.alloc(4) for _ in range(2)]           # 3 pages + an entry
    assert alloc.pages_in_use == 6 and alloc.free_entries == 0
    if short == "pages":
        alloc.free(held.pop())
        assert alloc.free_pages == 4 and alloc.free_entries == 1
        assert not alloc.can_alloc(6)                   # 5 pages of 4 free
    else:
        assert alloc.free_pages == 57 and not alloc.can_alloc(2)
    before = (alloc.pages_in_use, alloc.free_entries)
    with pytest.raises(PoolExhausted):
        alloc.alloc(6 if short == "pages" else 2)
    assert (alloc.pages_in_use, alloc.free_entries) == before
    for ids in held:
        alloc.free(ids)
    assert alloc.pages_in_use == 0 and alloc.free_entries == 2


def test_cache_rows_and_bytes_report_both_kinds(model):
    rows = model.cache_rows([5, 40])
    assert rows == {"full": 45 * 2, "window": (5 + 16) * 5}
    # published bytes: 2 (or 4) K/V heads of 24 + 16 float32 numbers
    assert model.row_bytes("full") == 2 * 40 * 4
    assert model.row_bytes("window") == 4 * 40 * 4
    assert model.cache_bytes([5, 40]) == {
        "full": 90 * 320, "window": 105 * 640}
    # an entry as stored: 16 rows of 4 heads of 128 + 16 numbers, 5 layers
    assert model.entry_bytes() == 16 * 4 * (128 + 16) * 4 * 5


def test_a_reused_entry_needs_no_reset(model):
    """A sequence seated on an entry another left takes nothing of it."""
    a, b = prompt(50, 21), prompt(13, 22)
    alone = through_the_caches(model, b, 9)
    through_the_caches(model, a, 44)                # fills the rings
    np.testing.assert_array_equal(through_the_caches(model, b, 9), alone)


# -- what is refused, by name -------------------------------------------------


def test_what_the_rings_at_an_earlier_row_would_need_is_refused_by_name(
        model):
    session = DecodeSession(model, max_slots=2, prefix_cache=object(),
                            spec_draft=object())
    assert session.prefix_cache is None and session._spec_draft is None
    pages = model.allocator.alloc(4)
    try:
        with pytest.raises(se.UnsupportedOverState, match="cached"):
            model.prefill([3] * 24, pages, cached_len=16)
    finally:
        model.allocator.free(pages)
    with pytest.raises(se.UnsupportedOverState, match="fork"):
        model.copy_page(1, 2)
    with pytest.raises(se.UnsupportedOverState, match="verify"):
        model.verify_chunk(np.zeros((2, 3), np.int64), [], None, None)
    with pytest.raises(se.UnsupportedOverState, match="rings as"):
        model.block.layer(1).mixer(None, jnp.zeros((2, 3, 32)), None,
                                   (None,) * 4, 1, None, 8)
    with pytest.raises(ValueError, match="outside 1..256"):
        model.prefill_bucket(257)
    with pytest.raises(ValueError, match="page size"):
        make(sliding_window=16)
    with pytest.raises(ValueError, match="full_attention"):
        make(layer_types=["full_attention", "linear_attention"] * 3
             + ["full_attention"])


# -- behind the session, and what it counts -----------------------------------


def test_session_serves_short_and_chunked_prompts(model):
    session = DecodeSession(model, max_slots=4)
    prompts = [prompt(n, 60 + n) for n in (6, 40, 90)]
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


def test_the_chunk_loop_and_the_step_count_what_the_metrics_read(model):
    def value(name, **labels):
        return metrics.REGISTRY.get(name).value(**labels)

    rows0 = value("decode_prefill_chunk_rows_total", over="ring")
    read0 = value("decode_full_rows_read_total")
    page0 = value("decode_run_pages_in_use_steps_total")
    hit0 = value("moe_experts_hit_total", phase="decode")
    ids = prompt(93, 31)
    through_the_caches(model, ids, 90)          # 64 + chunks of 16, 10 real
    assert value("decode_prefill_chunk_rows_total", over="ring") - rows0 == 26
    # three steps: the slot's rows after each, its own counted
    assert value("decode_full_rows_read_total") - read0 == 91 + 92 + 93
    # 93 rows reserved: 12 pages in use at each of the three steps
    assert value("decode_run_pages_in_use_steps_total") - page0 == 36
    assert value("moe_experts_hit_total", phase="decode") > hit0


def test_named_scopes_place_the_layers(model):
    cache = model._cache()
    step = dm._decode_step.trace(
        model.params, *cache[:2], np.zeros((4, 33), np.int32),
        np.zeros((4,), np.int32), np.zeros((4,), np.int32), heads=8,
        page_size=8, block=model.block, extra=cache[2:]).lower().as_text(
            debug_info=True)
    bucket = dm._prefill_bucket.trace(
        model.params, *cache[:2], np.zeros((64,), np.int32),
        (np.zeros((64,), np.int32), np.int32(0)), np.int32(3), heads=8,
        block=model.block, extra=cache[2:]).lower().as_text(debug_info=True)
    chunk = se._prefill_state_chunk.trace(
        model.params, *cache[:2], np.zeros((33,), np.int32),
        np.zeros((16,), np.int32), np.int32(3), heads=8, page_size=8,
        block=model.block, done=64, extra=cache[2:]).lower().as_text(
            debug_info=True)
    for text in (step, bucket, chunk):
        for scope in ("blk_mixer/attn_full", "blk_mixer/attn_window",
                      "blk_mlp/moe_router", "blk_head"):
            assert scope in text, scope
        assert "moe_shared" not in text
    assert "blk_mixer/attn_full/attn_chunk" in chunk
