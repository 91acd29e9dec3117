"""Ling-3.0-flash's block over the paged skeleton
(``models/ling_hybrid.py``) at toy widths, CPU, float32: the system
against the plain reference (``perf/reference/ling_hybrid_block.py``)
through the entries and the pages; the chunked rule and the step against
the row-by-row recurrence, the two kernels of ``pallas/kda.py``
interpreted against their XLA forms, with a head whose decays reach the
lower bound; the router's group step; the expert-parallel shares adding
up to the uncut layer; a latent page run and a state entry from the one
cache manager; what is refused by name; the scopes and the counters."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hybrid_models import lowered_texts, through_the_cache
from paddle_tpu import pallas as pk
from paddle_tpu.decode.paged_kv import CacheManager
from paddle_tpu.decode.session import (AdmissionRefused, BeamRequest,
                                       DecodeRequest, DecodeSession)
from paddle_tpu.decode.state_entry import UnsupportedOverState
from paddle_tpu.models import ling_hybrid as lh
from paddle_tpu.models import moe
from paddle_tpu.observability import metrics
from paddle_tpu.pallas import kda
from perf.reference import ling_hybrid_block as ref

F32 = jnp.float32
# toy widths that keep the shapes the kernels ask for: KDA heads of 128
# (a state entry's rows and a reference row are whole lanes), latent
# rows of 128 + 64 lanes, pages of 8 rows; a period of three, two
# leading dense layers, 4 held of 16 experts in 4 groups of which a row
# keeps 2
SIZES = dict(vocab=80, d_model=64, num_heads=4, num_layers=6,
             layer_group_size=3, first_k_dense_replace=2,
             qk_nope_head_dim=16, qk_rope_head_dim=64, v_head_dim=16,
             kv_lora_rank=128, linear_num_heads=2, linear_head_dim=128,
             dense_width=96, expert_width=16, shared_width=16,
             num_experts_published=16, held_experts=(0, 4),
             experts_per_tok=3, n_group=4, topk_group=2, max_len=128,
             num_pages=40, page_size=8, pages_per_seq=16, state_entries=5,
             dtype="float32")


def make(seed=3, **over):
    return lh.LingHybridLM(seed=seed, **{**SIZES, **over})


@pytest.fixture(autouse=True, scope="module")
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    """One model for the cases that leave it as they found it."""
    return make()


@pytest.fixture(params=["xla", "kernels"])
def path(request):
    """The XLA forms, or the Pallas kernels interpreted; each traced
    afresh (the mode is no part of a jitted program's key)."""
    on = request.param == "kernels"
    pk.enable(on, interpret=on)
    jax.clear_caches()
    try:
        yield request.param
    finally:
        pk.enable("auto", interpret=False)
        jax.clear_caches()


def prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, 80, n).tolist()


def reference(m, ids, rows=None, ablate=None, held=None):
    b, lat = m.block, m.block.latent
    return ref.forward(
        m.params, jnp.asarray(ids, jnp.int32), layer_types=b.layer_types,
        num_heads=m.heads, nope=lat.nope, rope_dim=lat.rope_dim,
        lin_heads=b.lin_heads, d_k=b.d_k, d_v=b.d_v,
        lower_bound=b.lower_bound, top_k=b.top_k, scale=b.scale,
        held=held or b.held, n_group=b.groups[0], topk_group=b.groups[1],
        eps=b.eps, theta=lat.theta, ablate=ablate, rows=rows)


def dispatched(kernel):
    return {p: pk._M_DISPATCH.value(kernel=kernel, path=p)
            for p in ("compiled", "interpret", "reference")}


# -- the system against the reference -----------------------------------------


@pytest.mark.parametrize("n", [9, 40, 70])
def test_prefill_then_decode_is_the_references_full_forward(path, n):
    """Prefill through the bucket's program (70 rows: a 128-row bucket,
    which the chunked kernel takes whole), then four tokens teacher-
    forced through the entries and the pages: the logits of all five
    rows against the reference's full forward."""
    m = make()
    ids, tokens = prompt(n, n), prompt(4, 100 + n)
    before = dispatched("kda_chunked"), dispatched("kda_step")
    got = through_the_cache(m, ids, tokens)
    want, _ = reference(m, ids + tokens, list(range(n - 1, n + 4)))
    assert ref.rel_rms(got, want) < 2e-5
    kind = "interpret" if path == "kernels" else "reference"
    chunked, step = dispatched("kda_chunked"), dispatched("kda_step")
    assert step[kind] - before[1][kind] == 4          # a KDA layer a trace
    fits = path == "kernels" and n > 64               # whole chunks of 128
    assert (chunked["interpret"] - before[0]["interpret"]
            == (4 if fits else 0))
    assert (chunked["reference"] - before[0]["reference"]
            == (0 if fits else 4))


def test_every_ablation_and_precision_moves_the_logits(model):
    """Each piece the reference can change is seen by the comparison at
    toy widths: no ablation reads as the program does."""
    ids = prompt(40, 7)
    rows = list(range(30, 40))
    want, masks = reference(model, ids, rows)
    assert masks.shape == (4, 40, 16) and bool(jnp.all(masks.sum(-1) == 3))
    for ablate in ref.ABLATIONS + ref.PRECISIONS:
        wrong, _ = reference(model, ids, rows, ablate)
        assert ref.rel_rms(wrong, want) > 1e-3, ablate


def test_session_tokens_are_the_references(model):
    prompts = [prompt(n, 40 + n) for n in (5, 17, 33)]
    session = DecodeSession(model, max_slots=4)
    reqs = [session.submit(DecodeRequest(p, max_new_tokens=4))
            for p in prompts]
    session.run(max_steps=400)
    for p, r in zip(prompts[:2], reqs):
        ids = list(p)
        for _ in range(4):
            logits, _ = reference(model, ids, [len(ids) - 1])
            ids.append(int(np.argmax(np.asarray(logits[0]))))
        assert r.result(1) == ids[len(p):]
    assert len(reqs[2].result(1)) == 4
    assert model.allocator.free_entries == 4
    assert model.allocator.pages_in_use == 0


# -- the rule: chunked, the step, the kernels ---------------------------------


def _rows(T, H=2, dk=128, dv=16, seed=0, to_the_bound=True):
    """Normalised q and k, v, the bounded per-channel log-decay and
    beta; with ``to_the_bound`` head 0's gate is driven far positive, so
    its decays sit at the lower bound e^-5 a token, the most a block of
    16 rows can span."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (T, H, dk), F32)
    k = jax.random.normal(ks[1], (T, H, dk), F32)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (T, H, dv), F32)
    x = jax.random.uniform(ks[3], (T, H, dk), F32, -9.0, 1.0)
    if to_the_bound:
        x = x.at[:, 0].set(30.0)
    g = -5.0 * jax.nn.sigmoid(x)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H), F32))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta, state):
    """Row by row, by the reference's own scan (its state starts at
    zero: ``state`` must)."""
    assert not np.asarray(state).any()
    return ref._recurrence(q, k, v, jnp.exp(g), beta, state_bf16=False)[0]


@pytest.mark.parametrize("to_the_bound", [False, True],
                         ids=["drawn", "at-the-bound"])
@pytest.mark.parametrize("T", [16, 50, 64, 200])
def test_the_chunked_rule_is_the_recurrence(T, to_the_bound):
    """Against the row-by-row recurrence, a head at the bound included:
    e^-5 a token a channel, the most a block of 16 rows can span."""
    q, k, v, g, beta = _rows(T, seed=T, to_the_bound=to_the_bound)
    state = jnp.zeros((2, 16, 128), F32)
    limit = 1e-5
    o, last = lh.chunked_kda(q, k, v, g, beta, state)
    want = _recurrence(q, k, v, g, beta, state)
    assert (float(jnp.min(g[:, 0])) < -4.99) == to_the_bound
    assert bool(jnp.all(jnp.isfinite(o)))
    assert ref.rel_rms(o, want) < limit
    # the state it hands on: one more row on it is the recurrence's next
    more = _rows(T + 1, seed=T, to_the_bound=to_the_bound)
    o1, _ = lh.step_kda(*(a[T] for a in more), last)
    want1 = _recurrence(*more, state)[T]
    assert ref.rel_rms(o1, want1) < limit


def test_the_chunked_rule_carries_a_state_and_skips_padding():
    """Two halves, the second from the first's state, are the whole;
    rows with g = 0 and beta = 0 (a bucket's padding) change nothing."""
    q, k, v, g, beta = _rows(96, seed=5)
    zero = jnp.zeros((2, 16, 128), F32)
    o, last = lh.chunked_kda(q, k, v, g, beta, zero)
    o_a, mid = lh.chunked_kda(*(a[:40] for a in (q, k, v, g, beta)), zero)
    o_b, end = lh.chunked_kda(*(a[40:] for a in (q, k, v, g, beta)), mid)
    assert ref.rel_rms(jnp.concatenate([o_a, o_b]), o) < 1e-5
    assert ref.rel_rms(end, last) < 1e-5
    pad = [jnp.pad(a, ((0, 32),) + ((0, 0),) * (a.ndim - 1))
           for a in (q, k, v)]
    pad += [jnp.pad(g, ((0, 32), (0, 0), (0, 0))),
            jnp.pad(beta, ((0, 32), (0, 0)))]
    _, padded = lh.chunked_kda(*pad, zero)
    assert ref.rel_rms(padded, last) < 1e-6


@pytest.mark.parametrize("T", [128, 384])
def test_the_chunked_kernel_is_its_xla_form(T):
    q, k, v, g, beta = _rows(T, seed=3)
    state = jax.random.normal(jax.random.key(9), (2, 16, 128), F32) * 0.1
    assert kda.chunked_fits(F32, T, 2, 16, 128, -5.0)
    o, last = kda.kda_chunked(q, k, v, g, beta, state, interpret=True)
    want_o, want_last = lh.chunked_kda(q, k, v, g, beta, state)
    assert bool(jnp.all(jnp.isfinite(o)))
    assert ref.rel_rms(o, want_o) < 1e-5              # a head at the bound
    assert ref.rel_rms(last, want_last) < 1e-5


@pytest.mark.parametrize("what, args", [
    ("a bfloat16 state", (jnp.bfloat16, 128, 2, 16, 128, -5.0)),
    ("half a chunk", (F32, 64, 2, 16, 128, -5.0)),
    ("keys of 96 lanes", (F32, 128, 2, 16, 96, -5.0)),
    ("a gate bounded at -6: 48 half a block", (F32, 128, 2, 16, 128, -6.0)),
    ("an unbounded gate", (F32, 128, 2, 16, 128, -float("inf"))),
])
def test_what_the_chunked_kernel_refuses(what, args):
    assert not kda.chunked_fits(*args), what


def test_the_step_kernel_is_its_xla_form():
    """Five slots on a pool of seven entries, two of them the null
    entry's: every entry a live slot names is advanced as ``step_kda``
    advances it, and no other entry is touched."""
    S, H, dv, wide = 5, 2, 16, 128
    pool = jax.random.normal(jax.random.key(1), (7, H, dv, wide), F32)
    at = jnp.asarray([3, 0, 5, 1, 0], jnp.int32)
    q, k, v, g, beta = _rows(S, seed=2)
    assert kda.step_fits(F32, H, dv, wide)
    o, new = kda.kda_step(pool, at, q, k, v, g, beta, interpret=True)
    want_o, want = lh.step_kda(q, k, v, g, beta, pool[at])
    live = np.asarray([0, 2, 3])
    assert ref.rel_rms(o[live], want_o[live]) < 1e-6
    assert ref.rel_rms(new[at[live]], want[live]) < 1e-6
    untouched = np.asarray([2, 4, 6])
    np.testing.assert_array_equal(np.asarray(new[untouched]),
                                  np.asarray(pool[untouched]))


# -- the router's group step --------------------------------------------------


def _old_route(m, wr, top_k, scores):
    """``moe.route`` as it stood before the group step, copied."""
    logits = jnp.dot(m, wr, preferred_element_type=F32)
    rank_by, weigh_by, weights_of = scores(logits)
    top, idx = jax.lax.top_k(rank_by, top_k)
    if weigh_by is not rank_by:
        top = jnp.take_along_axis(weigh_by, idx, axis=-1)
    return weights_of(top), idx


@pytest.mark.parametrize("groups", [None, (1, 1)])
@pytest.mark.parametrize("rule", ["softmax", "sigmoid"])
def test_route_without_a_group_step_is_bit_for_bit_what_it_was(rule, groups):
    ks = jax.random.split(jax.random.key(4), 3)
    m = jax.random.normal(ks[0], (33, 24), F32)
    wr = jax.random.normal(ks[1], (24, 16), F32)
    scores = (moe.softmax_scores if rule == "softmax" else
              moe.sigmoid_scores(jax.random.normal(ks[2], (16,)) * 0.1, 2.5))
    w, idx = moe.route(m, wr, 3, scores, groups=groups)
    old_w, old_idx = _old_route(m, wr, 3, scores)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(old_idx))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(old_w))
    # and the program: the same equations but for the scopes' names
    new = jax.make_jaxpr(lambda a, b: moe.route(a, b, 3, scores, groups))(
        m, wr)
    old = jax.make_jaxpr(lambda a, b: _old_route(a, b, 3, scores))(m, wr)
    assert [e.primitive.name for e in new.eqns] == [
        e.primitive.name for e in old.eqns]


def test_the_group_step_is_the_references():
    """8 groups of 8, 4 kept, top-8 among their 32: the chosen sets,
    the kept groups and the weights against the reference's sorts, with
    ties (whole-number logits) going to the lower group and index."""
    ks = jax.random.split(jax.random.key(6), 3)
    m = jnp.round(jax.random.normal(ks[0], (200, 16), F32))
    wr = jnp.round(jax.random.normal(ks[1], (16, 64), F32)) / 4
    b = jnp.round(jax.random.normal(ks[2], (64,), F32) * 2) / 8
    w, idx = moe.route(m, wr, 8, moe.sigmoid_scores(b, 2.5), groups=(8, 4))
    weight, mask, kept = ref._router(wr, b, m, top_k=8, scale=2.5,
                                     n_group=8, topk_group=4)
    chosen = np.zeros((200, 64), bool)
    np.put_along_axis(chosen, np.asarray(idx), True, axis=1)
    np.testing.assert_array_equal(chosen, np.asarray(mask))
    got = np.zeros((200, 64), np.float32)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=1)
    np.testing.assert_allclose(got, np.asarray(weight), rtol=1e-6)
    rank_by = jax.nn.sigmoid(m @ wr) + b
    np.testing.assert_array_equal(
        np.asarray(moe.kept_groups(rank_by, 8, 4)), np.asarray(kept))
    assert bool(jnp.all(kept.sum(-1) == 4))
    # every chosen expert lies in a kept group
    assert not (chosen & ~np.repeat(np.asarray(kept), 8, axis=1)).any()
    # and the step is felt: without it other experts are chosen
    _, free = moe.route(m, wr, 8, moe.sigmoid_scores(b, 2.5))
    assert (np.sort(np.asarray(free)) != np.sort(np.asarray(idx))).any()


def test_the_group_load_counts_live_rows_alone():
    ks = jax.random.split(jax.random.key(8), 5)
    m = jax.random.normal(ks[0], (12, 16), F32)
    wr = jax.random.normal(ks[1], (16, 16), F32)
    w3 = (jax.random.normal(ks[2], (16, 16, 8)),
          jax.random.normal(ks[3], (16, 16, 8)),
          jax.random.normal(ks[4], (16, 8, 16)))
    live = jnp.arange(12) < 7
    out = moe.routed_experts(m, wr, *w3, top_k=3, live=live, groups=(4, 2))
    assert len(out) == 4
    y, load, elsewhere, groups = out
    assert int(groups.sum()) == 7 * 2 and int(load.sum()) == 7 * 3
    plain = moe.routed_experts(m, wr, *w3, top_k=3, live=live)
    assert len(plain) == 3


# -- the shares add up --------------------------------------------------------


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Four chips of a 4-way expert-parallel group, each holding 4 of
    the 16 experts (one group of the router's four) and all of them the
    shared expert: the routed parts the four compute, and the shared
    expert's counted once, add up to what the uncut reference gives for
    the whole layer under the group step."""
    d, f, E, C = 64, 16, 16, 4
    whole = make(held_experts=(0, E))
    lp = whole.params["layers"][3]                     # a routed KDA layer
    m = jax.random.normal(jax.random.key(11), (37, d), F32)
    want, mask = ref.feed_forward(
        lp, m, top_k=3, scale=2.5, held=(0, E), n_group=4, topk_group=2,
        ablate=None)
    shared = ref.moe_ref._swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    total = shared
    hit = 0
    for rank in range(E // C):
        sl = slice(rank * C, (rank + 1) * C)
        routed, load, elsewhere, groups = moe.routed_experts(
            m, lp["wr"], lp["w_gate"][sl], lp["w_up"][sl], lp["w_down"][sl],
            top_k=3, scores=moe.sigmoid_scores(lp["b"], 2.5),
            held=(rank * C, C), groups=(4, 2))
        total = total + routed
        hit += int(load.sum())
        assert int(load.sum()) + int(elsewhere) == 37 * 3
        assert int(groups.sum()) == 37 * 2
    assert hit == 37 * 3 == int(mask.sum())
    assert ref.rel_rms(total, want) < 1e-5
    # and the block's own feed-forward on one share is that share's part
    share = make(held_experts=(4, 4))
    x = jax.random.normal(jax.random.key(12), (37, d), F32)
    lp1 = dict(lp, **{n: lp[n][4:8] for n in ("w_gate", "w_up", "w_down")})
    got, report = share.block.mlp(lp1, x, None)
    mm = ref.rms_norm(x, lp["w_post"], 1e-6)
    part, _ = ref.feed_forward(lp1, mm, top_k=3, scale=2.5, held=(4, 4),
                               n_group=4, topk_group=2, ablate=None)
    assert ref.rel_rms(got - x, part) < 5e-5   # x + y - x rounds at |x|
    assert report.shape == (4 + 1 + 4,)


# -- two resources from the one cache manager ---------------------------------


def test_a_latent_page_run_and_a_state_entry_from_the_one_manager(model):
    assert isinstance(model.allocator, CacheManager)
    assert model.block.layer_types == (lh.LINEAR, lh.LINEAR, lh.LATENT) * 2
    # ONE latent pool over the two latent layers, a placeholder beside it
    assert model.k_pool.shape == (2, 40, 8, 256)      # 128 + 64 -> 256 lanes
    assert model.v_pool.shape == (2, 1)
    assert model.state_pool.shape == (4, 5, 2, 128, 128)
    assert model.state_pool.dtype == jnp.float32
    assert model.conv_pool.shape == (4, 5, 18, 128)   # 3 rows of 768
    ids = model.allocator.alloc(model.context_pages([2] * 20, 4))
    try:
        assert len(ids) == 3 + 1
        table = model.pool_table(ids)
        assert table[model.full_pages] == model.allocator.entry_of(ids)
        assert list(table[:3]) == model.allocator.pages_of(ids)
    finally:
        model.allocator.free(ids)
    assert model.cache_rows([10, 30]) == {"latent": 80, "state": 8}
    assert model.cache_bytes([10, 30]) == {
        "latent": 80 * 256 * 4, "state": 2 * model.entry_bytes()}
    assert model.entry_bytes() == 4 * (2 * 128 * 128 * 4 + 18 * 128 * 4)


def test_gauges_and_health_show_both_resources():
    from paddle_tpu.decode.engine import GenerationEngine

    m = make()
    engine = GenerationEngine(m, max_slots=2, max_new_tokens=8)
    try:
        req = engine.submit(prompt(11, 80), max_new_tokens=8)
        assert len(req.result(60)) == 8
        info = engine.info()
        assert info["state_entries_total"] == 4
        assert set(info["cache_rows"]) == {"latent", "state"}
        assert set(info["cache_bytes"]) == {"latent", "state"}
    finally:
        engine.stop()
    assert metrics.REGISTRY.get("decode_state_entries").value(
        state="free") == 4


def test_a_reused_entry_equals_a_fresh_one(path):
    """A prompt's states and tails are written whole over its entry: a
    sequence seated on an entry another left reads as on a fresh one."""
    m = make()
    ids, tokens = prompt(21, 1), prompt(3, 2)
    fresh = through_the_cache(m, ids, tokens)
    through_the_cache(m, prompt(30, 3), prompt(5, 4))
    again = through_the_cache(m, ids, tokens)
    np.testing.assert_allclose(again, fresh, rtol=1e-5, atol=1e-6)


# -- refused by name ----------------------------------------------------------


def test_what_a_state_cannot_do_is_refused_by_name(model):
    session = DecodeSession(model, max_slots=2, prefix_cache=object(),
                            spec_draft=object())
    assert session.prefix_cache is None and session._spec_draft is None
    with pytest.raises(AdmissionRefused) as e:
        session.submit(BeamRequest([3, 4], beam_size=2))
    assert e.value.reason == "beam_unsupported"
    ids = model.allocator.alloc(3)
    try:
        with pytest.raises(UnsupportedOverState, match="cached"):
            model.prefill([3] * 12, ids, cached_len=8)
    finally:
        model.allocator.free(ids)
    with pytest.raises(UnsupportedOverState, match="fork"):
        model.copy_page(1, 2)
    with pytest.raises(UnsupportedOverState, match="verify"):
        model.verify_chunk(np.zeros((2, 3), np.int64), [], None, None)
    with pytest.raises(UnsupportedOverState, match="chunk of rows"):
        model.block.mixer(model.params["layers"][0], jnp.zeros((2, 3, 64)),
                          None, model._cache(), 0, None, 4)
    assert not (model.supports_prefix_cache or model.supports_fork
                or model.supports_verify)


@pytest.mark.parametrize("key", ["expert_swiglu_limits",
                                 "shared_swiglu_limits"])
def test_a_clamped_swiglu_is_refused_by_name(key):
    with pytest.raises(lh.UnsupportedSwigluLimit, match="layers \\[5\\]"):
        make(**{key: [0, 0, 0, 0, 0, 4, 4]})
    # a limit on a layer that is not kept is no layer's here
    make(**{key: [0] * 6 + [4, 7]})


def test_the_pattern_is_layer_group_size():
    assert lh.layer_types_of(6, 6) == (lh.LINEAR,) * 5 + (lh.LATENT,)
    assert lh.layer_types_of(12, 6).count(lh.LATENT) == 2
    with pytest.raises(ValueError, match="both kinds"):
        make(num_layers=2)


# -- scopes and counters ------------------------------------------------------


def test_every_program_names_its_scopes(model):
    texts = lowered_texts(model)
    step, prefill = texts["_decode_step"], texts["_prefill_bucket"]
    for text in (step, prefill):
        for scope in ("blk_mixer/lin_attn_gate/", "blk_mixer/lin_attn/",
                      "blk_mixer/lin_attn/lin_attn_conv/",
                      "blk_mixer/attn_latent/", "blk_mlp/moe_router/",
                      "blk_mlp/moe_dispatch/moe_group/",
                      "blk_mlp/moe_shared/", "moe_experts/"):
            assert scope in text, scope
        # the gate stands beside lin_attn, not inside it
        assert "lin_attn/lin_attn_gate" not in text
    assert "blk_mixer/lin_attn/lin_attn_state/" in step
    assert "blk_mixer/lin_attn/lin_attn_scan/" in prefill
    assert "attn_latent/attn_latent_absorb" in step
    assert "attn_latent/attn_latent_expand" in prefill


def test_the_kernels_are_in_the_programs_lowered_for_a_tpu():
    """Lowered for a TPU with the kernels on and not interpreted, the
    decode step holds the ``kda_step`` call under ``lin_attn_state`` and
    a 128-row bucket the ``kda_chunked`` call under ``lin_attn_scan``."""
    pk.enable(True, interpret=False)
    jax.clear_caches()
    try:
        texts = lowered_texts(make(), bucket=128, platforms=("tpu",))
    finally:
        pk.enable("auto", interpret=False)
        jax.clear_caches()
    # (a name is written once in a lowered text's table of locations)
    assert ("lin_attn/lin_attn_state/kda_step/pallas_call"
            in texts["_decode_step"])
    assert ("lin_attn/lin_attn_scan/kda_chunked/pallas_call"
            in texts["_prefill_bucket"])
    assert "kda_chunked" not in texts["_decode_step"]


def test_the_groups_are_counted_by_phase():
    fam = metrics.REGISTRY.get("moe_groups_chosen_total")

    def total(phase):
        return sum(fam.value(group=str(g), phase=phase) for g in range(4))

    m = make()
    before = total("prefill"), total("decode")
    through_the_cache(m, prompt(20, 5), prompt(2, 6))
    # 4 routed layers x 2 groups kept: 20 live rows of the bucket, then
    # one live slot of four at each of two steps
    assert total("prefill") - before[0] == 4 * 2 * 20
    assert total("decode") - before[1] == 4 * 2 * 2


# -- which path runs: ``pallas/__init__.py``'s rule, by fits() alone ----------

_STEP, _CHUNKED = ("float32", 32, 128, 128), ("float32", 8192, 32, 128, 128,
                                              -5.0)


@pytest.mark.parametrize("kernel, shape, mode, on_tpu, interpret, want", [
    ("kda_step", _STEP, "auto", True, False, "compiled"),
    ("kda_step", ("float32", 2, 16, 128), "auto", False, True, "interpret"),
    ("kda_step", _STEP, "auto", False, False, "reference"),
    ("kda_step", ("float32", 2, 10, 128), "on", True, False, "reference"),
    ("kda_step", ("bfloat16", 32, 128, 128), "on", True, False, "reference"),
    ("kda_step", _STEP, "off", True, False, "reference"),
    ("kda_chunked", _CHUNKED, "auto", True, False, "compiled"),
    ("kda_chunked", ("float32", 128, 32, 128, 128, -5.0), "auto", True,
     False, "compiled"),
    ("kda_chunked", ("float32", 128, 2, 16, 128, -5.0), "auto", False, True,
     "interpret"),
    ("kda_chunked", _CHUNKED, "auto", False, False, "reference"),
    ("kda_chunked", ("float32", 64, 32, 128, 128, -5.0), "on", True, False,
     "reference"),
    ("kda_chunked", ("float32", 8192, 32, 128, 128, -6.0), "on", True,
     False, "reference"),
    ("kda_chunked", _CHUNKED, "off", True, False, "reference"),
])
def test_kernel_policy(monkeypatch, kernel, shape, mode, on_tpu, interpret,
                       want):
    """(kernel, shape, mode, backend, interpret) -> the path that runs,
    and the ``pallas_dispatch_total{kernel, path}`` label it counts: no
    threshold, ``fits()`` and the mode alone."""
    saved = dict(pk._STATE)
    monkeypatch.setattr(pk, "tpu_backend", lambda: on_tpu)
    pk.enable(mode, interpret=interpret)
    try:
        before = dispatched(kernel)
        use = getattr(pk, "use_" + kernel)(*shape)
        after = dispatched(kernel)
    finally:
        pk._STATE.update(saved)
    assert {p: after[p] - before[p] for p in after
            if after[p] != before[p]} == {want: 1}
    assert use == (want != "reference")
