"""OLMoE on the paged decoder (``paddle_tpu/models/olmoe.py``, the
routed layer of ``paddle_tpu/models/moe.py``) against the plain float32
reference the benchmark keeps (``perf/reference/olmoe_block.py``), at
a small size on the CPU with seeded random float32 weights; and the
GPT-2 block through the same skeleton against values recorded from the
tree before the skeleton took a block.

TOL: system and reference are both float32 here and differ only in the
order of their sums (paged attention against dense, sorted grouped
GEMMs against dense masked experts): relative RMS of the logits reads
2e-7 to 6e-7.  1e-5 leaves room for another CPU's code generation and
is three to four orders under what a dropped RoPE (0.33), a dropped q/k
norm (0.39), a renormalised (0.046) or a shortened top-k (0.012) or
float8 weights (0.068) read; a position off by one reads like a dropped
RoPE.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.models import moe  # noqa: E402
from paddle_tpu.models.olmoe import OlmoeLM  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from perf.reference import olmoe_block as ref  # noqa: E402

TOL = 1e-5
SIZES = dict(vocab=101, d_model=64, num_heads=4, num_layers=2,
             num_experts=8, experts_per_tok=2, expert_width=32,
             max_len=128, num_pages=24, page_size=8, pages_per_seq=8,
             dtype="float32", eos_id=-1)
S = 4       # slots of the hand-driven steps


@pytest.fixture(scope="module")
def model():
    return OlmoeLM(seed=5, **SIZES)


def _reference(model, ids, ablate=None, rows=None):
    return ref.forward(model.params, jnp.asarray(ids, jnp.int32),
                       num_heads=model.heads, top_k=model.block.top_k,
                       eps=model.block.eps, theta=model.block.theta,
                       ablate=ablate, rows=rows)[0]


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, SIZES["vocab"], n).tolist()


def _seat(model, slot, pages, ctx):
    tables = np.zeros((S, model.pages_per_seq), np.int32)
    tables[slot] = model.pool_table(pages)
    lens = np.zeros((S,), np.int32)
    lens[slot] = ctx
    return tables, lens


def _decode_rows(model, prompt, tokens, slot=2, cached_len=0):
    """Prefill ``prompt``, then feed ``tokens`` one step each through
    the paged cache: the len(tokens) + 1 logits rows and the pages."""
    pages = model.allocator.alloc(model.context_pages(prompt, len(tokens)))
    if cached_len:
        model.prefill(prompt[:cached_len], pages)
    ctx, _, last = model.prefill(prompt, pages, cached_len=cached_len)
    rows = [np.asarray(last)]
    tables, lens = _seat(model, slot, pages, ctx)
    for tok in tokens:
        step = np.full((S, 1), model.bos_id, np.int64)
        step[slot, 0] = tok
        logits, _ = model.decode(step, [], tables, lens)
        lens[slot] += 1
        rows.append(logits[slot])
    return np.stack(rows), pages


# -- the routed layer ------------------------------------------------------


def _router(kind, rng, d, E):
    wr = rng.randn(d, E).astype(np.float32)
    if kind == "tie":
        wr[:, 5] = wr[:, 2]           # experts 2 and 5 tie on every row
    elif kind == "one_expert":
        wr[:, 3] = 0.0                # rows are positive: expert 3 ...
        wr[0, 3] = 50.0               # ... wins every row by far
    return wr


# moe.expert_path at 8 experts top-3: rows too few to hit most experts
# and rows past DENSE_MAX_ROWS take the grouped GEMM, those between the
# dense pass
PATH_OF_ROWS = {4: "grouped", 19: "dense", moe.DENSE_MAX_ROWS + 19: "grouped"}
MANY = max(PATH_OF_ROWS)


def _toy_layer(kind, R):
    rng = np.random.RandomState(3)
    d, E, f = 16, 8, 12
    m = np.abs(rng.randn(R, d)).astype(np.float32)
    if kind == "one_expert":
        m[:, 0] += 1.0                # no row too small for expert 3
    wr = _router(kind, rng, d, E)
    wg, wu = (rng.randn(E, d, f).astype(np.float32) * 0.3 for _ in "gu")
    wd = rng.randn(E, f, d).astype(np.float32) * 0.3
    return m, wr, wg, wu, wd


@pytest.mark.parametrize("R", list(PATH_OF_ROWS),
                         ids=["few_grouped", "dense", "many_grouped"])
@pytest.mark.parametrize("kind", ["random", "tie", "one_expert"])
def test_routed_layer_matches_dense_all_experts(kind, R):
    k = 3
    m, wr, wg, wu, wd = _toy_layer(kind, R)
    assert moe.expert_path(R, k, wr.shape[1]) == PATH_OF_ROWS[R]
    live = np.arange(R) % 3 != 0
    y, load, _ = moe.routed_experts(jnp.asarray(m), wr, wg, wu, wd, top_k=k,
                                    live=jnp.asarray(live))
    p = jax.nn.softmax(jnp.asarray(m @ wr), axis=-1)
    mask = ref.top_k_mask(p, k)
    want = ref.experts(jnp.asarray(m), mask, p, jnp.asarray(wg),
                       jnp.asarray(wu), jnp.asarray(wd))
    assert ref.rel_rms(y, want) < TOL
    np.testing.assert_array_equal(
        np.asarray(load), np.asarray(mask)[live].sum(axis=0))
    assert int(mask.sum()) == R * k
    if kind == "tie":                 # never expert 5 without expert 2
        assert not np.any(np.asarray(mask)[:, 5] & ~np.asarray(mask)[:, 2])
    if kind == "one_expert":
        assert int(load[3]) == live.sum()


@pytest.mark.parametrize("kind", ["random", "tie", "one_expert"])
def test_the_two_paths_compute_the_same_sum(kind):
    """The same rows as one call of many rows (the grouped GEMM) and as
    two calls of few (the dense pass): a row's sum does not depend on
    the rows beside it."""
    m, wr, wg, wu, wd = _toy_layer(kind, MANY)
    live = np.arange(MANY) % 3 != 0
    half = MANY // 2
    assert moe.expert_path(MANY - half, 3, wr.shape[1]) == "dense"

    def layer(rows):
        return moe.routed_experts(
            jnp.asarray(m[rows]), wr, wg, wu, wd, top_k=3,
            live=jnp.asarray(live[rows]))

    y, load, away = layer(slice(None))
    (y0, load0, away0), (y1, load1, away1) = (
        layer(slice(half)), layer(slice(half, None)))
    np.testing.assert_allclose(np.concatenate([y0, y1]), y, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(load0 + load1, load)
    assert int(away) == int(away0) == int(away1) == 0


def _primitives(jaxpr):
    """(primitive name, shapes of its operands) of every equation,
    nested jaxprs (a loop's body, a pjit) included."""
    out = []
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name,
                    [tuple(v.aval.shape) for v in eqn.invars]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _primitives(sub)
    return out


@pytest.mark.parametrize("held", [None, (2, 2)], ids=["all", "a_share"])
def test_with_every_expert_held_the_grouped_path_is_one_pass(held):
    """Every expert held: the grouped path traces what it traced before
    a share learned to run over its own assignments alone: no loop, no
    branch, three grouped GEMMs over all ``R x k`` sorted rows, the
    un-sort and the weighted sum over k (its lowered text is the
    parent's, letter for letter: checked once, PR 38).  A share of the
    same layer loops over blocks and holds nothing of ``R x k`` rows by
    ``d`` columns."""
    k = 3
    m, wr, wg, wu, wd = _toy_layer("random", MANY)
    sl = slice(None) if held is None else slice(held[0], sum(held))
    live = jnp.arange(MANY) % 3 != 0
    prims = _primitives(jax.make_jaxpr(
        lambda m, live: moe.routed_experts(
            m, wr, wg[sl], wu[sl], wd[sl], top_k=k, live=live, held=held))(
                jnp.asarray(m), live).jaxpr)
    names = [n for n, _ in prims]
    gemm_rows = [shapes[0][0] for n, shapes in prims
                 if n == "ragged_dot_general"]
    wide = [shapes for _, shapes in prims for shape in shapes
            if len(shape) >= 2 and shape[0] == MANY * k
            and shape[-1] == m.shape[1]]
    if held is None:
        assert not {"while", "cond"} & set(names)
        assert gemm_rows == [MANY * k] * 3 and wide
    else:
        B = moe.grouped_block_rows(MANY, k, held[1], wr.shape[1])
        assert names.count("while") == 1 and "cond" not in names
        assert B < MANY * k and gemm_rows == [B] * 3 and not wide


def test_expert_path_is_one_interval_of_the_row_count():
    """The rule: the two cells' decode steps (32 slots over 64 experts,
    64 over 128, top-8) take the dense pass, every prefill bucket of
    their ladders over the threshold the grouped GEMM, and so does a
    step of so few slots that most experts get no row; in the row count
    the dense pass has one interval."""
    from paddle_tpu.bucket import bucket_ladder

    cells = [(32, 8, 64), (64, 8, 128)]             # (slots, top-k, E)
    ladder = [b for b in bucket_ladder(4096) + (4608,) if b >= 128]
    over = [b for b in ladder if b > moe.DENSE_MAX_ROWS]
    assert over[0] == 2 * moe.DENSE_MAX_ROWS and len(over) >= 3
    for slots, k, E in cells:
        assert moe.expert_path(slots, k, E) == "dense"
        assert all(moe.expert_path(b, k, E) == "grouped" for b in over)
        assert all(moe.expert_path(b, k, E) == "dense"
                   for b in ladder if b not in over)
        assert moe.expert_path(4, k, E) == "grouped"
        paths = [moe.expert_path(r, k, E) for r in range(1, 5000)]
        dense = [r for r, p in enumerate(paths, 1) if p == "dense"]
        first = -(-moe.DENSE_MIN_PER_EXPERT * E // k)
        assert dense == list(range(first, moe.DENSE_MAX_ROWS + 1))
        assert set(paths) == {"dense", "grouped"}


def _path_counts(snap):
    return {(v["labels"]["path"], v["labels"]["phase"]): v["value"]
            for v in snap.get("moe_expert_path_total",
                              {"values": []})["values"]}


def test_expert_path_counter_counts_a_routed_layer_a_call(model):
    """``moe_expert_path_total``: one a routed layer a call, under the
    rule's answer for the call's rows and the call's phase."""
    def moved(before):
        after = _path_counts(metrics.snapshot())
        return {key: after[key] - before.get(key, 0) for key in after
                if after[key] != before.get(key, 0)}

    L = SIZES["num_layers"]
    before = _path_counts(metrics.snapshot())
    _, pages = _decode_rows(model, _prompt(21), _prompt(3, seed=1))
    model.allocator.free(pages)
    # one prefill in the 64-row bucket; three steps of S = 4 rows, too
    # few to hit most of the 8 experts with two choices each
    assert moved(before) == {("dense", "prefill"): L,
                             ("grouped", "decode"): 3 * L}
    before = _path_counts(metrics.snapshot())
    model._observe("prefill", np.ones((L, 8), np.int32),
                   2 * moe.DENSE_MAX_ROWS)
    assert moved(before) == {("grouped", "prefill"): L}


# -- prefill and decode through the paged cache -----------------------------


@pytest.fixture(scope="module")
def decoded(model):
    """A 21-token prompt prefilled, then 8 tokens through the cache:
    (all ids, the rows of the reference to compare, the 9 logits
    rows)."""
    prompt, tokens = _prompt(21), _prompt(8, seed=1)
    got, pages = _decode_rows(model, prompt, tokens)
    model.allocator.free(pages)
    return prompt + tokens, range(len(prompt) - 1, len(prompt) + 8), got


def test_prefill_then_decode_logits_match_the_reference(model, decoded):
    ids, rows, got = decoded
    want = _reference(model, ids, rows=rows)
    assert got.shape == want.shape == (9, SIZES["vocab"])
    assert ref.rel_rms(got, want) < TOL
    for row in range(9):              # no single row hides in the mean
        assert ref.rel_rms(got[row], want[row]) < TOL


@pytest.mark.parametrize("ablate", ref.ABLATIONS)
def test_tolerance_catches_each_ablation(model, decoded, ablate):
    ids, rows, got = decoded
    wrong = _reference(model, ids, ablate=ablate, rows=rows)
    assert ref.rel_rms(got, wrong) > 1000 * TOL


def test_suffix_prefill_over_cached_pages(model):
    """RoPE at cached_len + i, the chunk kernel's path."""
    prompt, tokens = _prompt(29, seed=2), _prompt(3, seed=3)
    got, pages = _decode_rows(model, prompt, tokens, cached_len=16)
    model.allocator.free(pages)
    want = _reference(model, prompt + tokens,
                      rows=range(len(prompt) - 1, len(prompt) + 3))
    assert ref.rel_rms(got, want) < TOL


def test_verify_chunk_equals_single_steps(model):
    prompt, chunk = _prompt(13, seed=4), _prompt(4, seed=5)
    single, pages = _decode_rows(model, prompt, chunk, slot=1)
    model.allocator.free(pages)
    pages = model.allocator.alloc(model.context_pages(prompt, 4))
    ctx, _, _ = model.prefill(prompt, pages)
    tables, lens = _seat(model, 1, pages, ctx)
    tokens = np.full((S, 4), model.bos_id, np.int64)
    tokens[1] = chunk
    logits, _ = model.verify_chunk(tokens, [], tables, lens)
    model.allocator.free(pages)
    assert ref.rel_rms(logits[1], single[1:]) < TOL
    want = _reference(model, prompt + chunk,
                      rows=range(len(prompt), len(prompt) + 4))
    assert ref.rel_rms(logits[1], want) < TOL


def test_copy_page_splits_a_shared_page(model):
    """CoW: a fork that copies the last page and decodes on gives the
    logits of the unforked sequence."""
    prompt, tok = _prompt(11, seed=6), 7
    pages = model.allocator.alloc(2)
    ctx, _, _ = model.prefill(prompt, pages)
    fork = [pages[0]] + model.allocator.alloc(1)
    model.copy_page(pages[1], fork[1])
    tables = np.zeros((S, model.pages_per_seq), np.int32)
    tables[0], tables[3] = model.pool_table(pages), model.pool_table(fork)
    lens = np.array([ctx, 0, 0, ctx], np.int32)
    step = np.full((S, 1), tok, np.int64)
    logits, _ = model.decode(step, [], tables, lens)
    model.allocator.free(pages + fork[1:])
    np.testing.assert_array_equal(logits[0], logits[3])
    want = _reference(model, prompt + [tok], rows=[len(prompt)])
    assert ref.rel_rms(logits[0], want[0]) < TOL


# -- the load counters -------------------------------------------------------


def _moe_counters():
    snap = metrics.snapshot()
    return {(name, v["labels"]["phase"]): v["value"]
            for name in ("moe_assignments_total", "moe_experts_hit_total",
                         "moe_expert_load_max_total")
            for v in snap.get(name, {"values": []})["values"]}


def test_load_counters_ignore_padding_and_inactive_slots(model):
    L, k, E = SIZES["num_layers"], SIZES["experts_per_tok"], 8
    prompt = _prompt(13, seed=8)      # bucket 64: 51 padding rows
    pages = model.allocator.alloc(model.context_pages(prompt, 2))
    c0 = _moe_counters()
    ctx, _, last = model.prefill(prompt, pages)
    c1 = _moe_counters()
    d = {key: c1[key] - c0.get(key, 0) for key in c1}
    assert d["moe_assignments_total", "prefill"] == 13 * k * L
    assert d.get(("moe_assignments_total", "decode"), 0) == 0
    assert L <= d["moe_experts_hit_total", "prefill"] <= L * E
    assert d["moe_expert_load_max_total", "prefill"] <= 13 * L
    tables, lens = _seat(model, 2, pages, ctx)    # one live slot of four
    step = np.full((S, 1), model.bos_id, np.int64)
    model.decode(step, [], tables, lens)
    c2 = _moe_counters()
    d = {key: c2[key] - c1.get(key, 0) for key in c2}
    model.allocator.free(pages)
    assert d["moe_assignments_total", "decode"] == k * L
    assert d["moe_experts_hit_total", "decode"] == k * L
    assert d["moe_expert_load_max_total", "decode"] == L
    assert d["moe_assignments_total", "prefill"] == 0


def test_named_scopes_place_the_routed_layer(model):
    """Every program that runs the block carries the four moe_ scopes
    in its op_names (what the benchmark's readers match), under the
    skeleton's ``blk_mlp`` since PR 51."""
    from paddle_tpu.decode import model as dm

    text = dm._decode_step.lower(
        model.params, model.k_pool, model.v_pool,
        np.zeros((S, model.pages_per_seq), np.int32),
        np.zeros((S,), np.int32), np.zeros((S,), np.int32),
        heads=model.heads, page_size=model.page_size,
        block=model.block).as_text(debug_info=True)
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine"):
        assert f"_decode_step)/blk_mlp/{scope}/" in text, scope


# -- the GPT-2 block through the same skeleton -------------------------------

# float32 bytes of the prefill's logits row and of three decode steps'
# rows, recorded from the tree before this skeleton took a block
# (commit 9b7c80b, ``TinyDecoderLM._prefill_bucket`` / ``_decode_step``
# with the block's arithmetic written inside them), XLA CPU, one
# device.  In a process with the same flags the bytes are equal (and
# the four compiled programs' texts are, metadata apart: CHANGES.md,
# PR 26); XLA:CPU sums a dot in another order under other flags or
# machine features (8 virtual devices, a cached executable): 7.5e-8
# here on logits of 0.3.  GOLDEN_ATOL allows that and nothing a wrong
# position, norm or weight could hide in (those read over 1e-3).
GOLDEN_ATOL = 1e-6
GOLDEN = json.loads('''["03e81a3d8ce6d5bd710343bed6351bbdfcaa81bd88a4bfbd80efb33c9db4a2bd2c787b3c3145993dded683bd8e8f8c3d8092b2bcbe6a633d1ab5243e2660edbc112cdcbd6e30743e0a50d8bd2c289b3e41e2233e2c5017bcf5a8483e42b799bdd8086ebc90053ebedbc33bbe8648f03d10e33fbe0a27e9bc874415be822121be38e21a3cf4a9b7bc6e6aaebdeb3fbdbd466f19bece4380bc6255043d88e3fb3d99f1fd3d170c4b3d1e101dbd2aa58abd9cfd8d3c72b1d23c0ff61ebe9d3b8d3dfe459b3dcf16a1bcf96baf3d410e873d6075523d0882403db6943abea6020bbd00d754bb34b4fd3ce60817be9a0c6f3e90c5a53d52760fbe8e3b57be2aa3d1bd", "d7bd2f3e88611dbe0df3d8bc07e03bbb336fd2ba63a363bd0222753d499ac2bdfad911bef00efd3c3dd90cbdf8b0f63d68b9da3dc81a56bd2f4f82bb253234be2e73cc3da0114b3c38eb6cbe8fe9013f07911ebd9525f3bdbff37fbcda683b3e7323b6bd7270a6bc6e79743e679e993e79090d3d598375bdd4c49dbdfd37073d5ff1623dab6972bd3b01353d33f35e3dde4eb7be89f09a3debf69d3d6453023ea505993bae7f38be71399d3efbadab3dea5cf5ba8342bb3d537b3fbdef8d0ebe7337aabd0e34fbbda59d103bc37f133d3183383ec31788bde0ca06bec2e73cbd3405793b7157113e3fa58dbe17c2853d1aeb653dd87b29be8f550abe423071be", "942fea3dfce456bd2275d7bdd295873ccd42bdbc7cb88a3d1986c23c79b00fbecc06e43df393d23d3be1f6bddc619f3c2ee78cbc589a8cbd3d0e053c847352bddac802bd2438733ef733f2bdc82c1c3ff44cc6bdb7d18bbdb9ed233ec093b9bcf7607b3cc42ca6bd95f67c3dbe7d503e5dff78bd599dfd3d39a2823d0d9515be5a27c5bdafbc0d3d31e392bc8c8b093e4e8174be3bfa483dfdee873e729adc3c8b8e4d3dbe695cbebc4ba93de5098dbcc2f8a33d741f2c3ec58eb7bda00d1cbdd97319be039fc23c057b1e3eac1bfb3d3d40923db8ff3dbe7c16c6bda675adbbfb31c5bcf3b7073e73d2ebbdb8ca533dae88f03c7fdca2bdb6935dbe788c02be", "4d2b163effb854bec891ecbde01485bd477727bdea066dbdedff253d3160efbd4bfdebbd2879683dd0abaabd192b113ef1848e3c5284013a2802013edba344bd5985193d214a303e70694bbe3201133f6d77a5bc4a98e0bd4e25ccbb56cdebbd297072bdf79e27be0c255b3e343c813e2222b4bc8935ffbcc3325a3de5a934bdb8e790bc46fe503d5cea5fbd8993b3bb4b308ebebb6d9c3d3a29c53d6c84a9bdd9c8b33d0a430bbe97c9d13dc15468bb9ac5ad3d95e8053e993da1bd5ce3bfbdc10608bef78316bee9cef73de2f58e3c0fcede3dade544beafb590bd6a85543c68868dbd93b3653de1942bbe37ad263dc980613d443836beba8749be88f819be"]''')


def test_gpt2_block_matches_values_recorded_from_the_parent():
    from paddle_tpu.decode.model import TinyDecoderLM

    m = TinyDecoderLM(vocab=64, d_model=32, num_heads=4, num_layers=2,
                      max_len=64, num_pages=16, page_size=8,
                      pages_per_seq=8, seed=7)
    prompt = [3, 9, 27, 17, 51, 25, 11, 33, 35, 41, 59]
    pages = m.allocator.alloc(m.context_pages(prompt, 4))
    ctx, _, last = m.prefill(prompt, pages)
    rows = [np.asarray(last, np.float32)]
    tables = np.zeros((3, 8), np.int32)
    tables[2] = m.pool_table(pages)
    lens = np.zeros((3,), np.int32)
    lens[2] = ctx
    for _ in range(3):
        step = np.full((3, 1), 1, np.int64)
        step[2, 0] = int(np.argmax(rows[-1]))
        logits, _ = m.decode(step, [], tables, lens)
        lens[2] += 1
        rows.append(np.asarray(logits[2], np.float32))
    want = [np.frombuffer(bytes.fromhex(h), np.float32) for h in GOLDEN]
    for got, w in zip(rows, want):
        np.testing.assert_allclose(got, w, rtol=0, atol=GOLDEN_ATOL)
