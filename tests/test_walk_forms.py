"""The walk over a page run on grouped heads against its reference,
interpreted on the CPU, a case a (layout, chunk rows, query dtype): the
layouts the generate cells store their pages in, each through the body
``attention.page_form`` gives it (PR 63: a row-major bfloat16 page is
consumed as it is stored, a float32 pool and heads-major pages keep the
widened body).  Every case seats an empty seat, a chunk with nothing
cached, one cached row, a chunk that ends and one that starts on a
page's edge, and a ragged rest.
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.decode import attention as A

BF16, F32 = jnp.bfloat16, jnp.float32


@dataclasses.dataclass(frozen=True)
class Layout:
    name: str
    heads: int              # stored K/V heads a page
    group: int              # query heads a stored head
    lanes: int              # a stored key's
    value_lanes: int
    pool: object = BF16
    heads_major: bool = False
    pack: int = 1           # real heads side by side in a stored row
    form: str = "stored"    # what ``page_form`` gives a chunk on it


LAYOUTS = [
    # ``models/mimo_v2.py``: keys of 192 at 256 lanes on values of 128
    Layout("mimo", 4, 16, 256, 128),
    # ``models/granite_hybrid.py`` / ``lfm2_moe.py``: two 64-wide heads a
    # 128-lane row, a query's numbers in its own head's lanes
    Layout("packed", 4, 8, 128, 128, pack=2),
    # ``models/exaone_moe.py``
    Layout("k_exaone", 8, 8, 128, 128),
    # ``models/phi4_flash.py``: ten stored heads, their rows together
    Layout("phi_heads_major", 10, 4, 128, 128, heads_major=True,
           form="widened"),
    Layout("float32_pool", 2, 4, 128, 128, pool=F32, form="widened"),
]
PAGE, COLUMNS = 8, 6


@dataclasses.dataclass(frozen=True)
class Case:
    layout: Layout
    rows: int               # T: the chunk's rows a slot
    q: object               # the query's dtype
    scores: bool = False    # hold the scores, not the outputs

    def __str__(self):
        return (f"{self.layout.name}-T{self.rows}-q_"
                f"{jnp.dtype(self.q).name}" + ("-scores" * self.scores))


CASES = [Case(lay, T, q) for lay in LAYOUTS
         for T, q in ((1, BF16), (1, F32), (3, F32))]
CASES.append(Case(LAYOUTS[0], 1, BF16, scores=True))


def _lens(T):
    """Rows cached before the chunk, a slot: an empty seat, nothing
    cached, one cached row, the chunk's last row on a page's edge, its
    first row on one, and a ragged rest."""
    return np.array([-T, 0, 1, PAGE - T, PAGE, 2 * PAGE + 3, 4 * PAGE + 5,
                     5 * PAGE - T], np.int32)


def _inputs(case, seed=0):
    lay, T = case.layout, case.rows
    rng = np.random.RandomState(seed)
    lens = _lens(T)
    S, N = len(lens), len(lens) * COLUMNS + 1
    Hq = lay.heads * lay.group
    q = rng.randn(S, T, Hq, lay.lanes).astype(np.float32)
    if lay.pack > 1:
        # query head i of a stored head: its numbers in the lanes of real
        # head ``i // (group / pack)`` of the row, zeros in the others'
        mine = np.repeat(np.eye(lay.pack), lay.group // lay.pack, axis=0)
        lane_of = np.repeat(mine, lay.lanes // lay.pack, axis=1)
        q = q * np.tile(lane_of, (lay.heads, 1))[None, None]
    shape = ((N, lay.heads, PAGE) if lay.heads_major
             else (N, PAGE, lay.heads))
    k = jnp.asarray(rng.randn(*shape, lay.lanes), lay.pool)
    v = jnp.asarray(rng.randn(*shape, lay.value_lanes), lay.pool)
    # bfloat16's numbers in either dtype: the reference reads the same q
    q = jnp.asarray(q, BF16).astype(case.q)
    tables = jnp.asarray(1 + rng.permutation(N - 1)[:S * COLUMNS].reshape(
        S, COLUMNS), jnp.int32)
    return q, k, v, tables, jnp.asarray(lens)


@pytest.mark.parametrize("case", CASES, ids=str)
def test_the_grouped_walk_is_its_reference(case):
    lay, T = case.layout, case.rows
    assert A.page_form(lay.pool, lay.pool, lay.heads_major,
                       T * lay.group) == lay.form
    if case.scores:
        return _scores_are_the_float32_references(case)
    q, k, v, tables, lens = _inputs(case)
    got = A.ragged_paged_attention_gqa(q, k, v, tables, lens, interpret=True,
                                       heads_major=lay.heads_major)
    want = A.ragged_paged_attention_gqa_reference(
        q.astype(F32), k, v, tables, lens, heads_major=lay.heads_major)
    assert got.dtype == q.dtype and got.shape == want.shape
    seated = np.asarray(lens) + T > 0
    assert not np.asarray(got.astype(F32))[~seated].any()   # zeros
    got, want = (np.asarray(a.astype(F32))[seated] for a in (got, want))
    if case.q == F32:
        # the grouped kernel's tolerance in ``tests/test_phi4_flash.py``;
        # ``test_granite_hybrid.py`` holds 2e-6 absolute
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6)
    else:
        # a bfloat16 output: the reference's, rounded once, to one place
        np.testing.assert_allclose(
            got, np.asarray(jnp.asarray(want, BF16).astype(F32)),
            rtol=2.0 ** -7, atol=2e-6)


def _scores_are_the_float32_references(case):
    """The "same mathematics" of the stored form: bfloat16 q against
    bfloat16 keys as they are stored gives the float32 dot of the widened
    operands to float32 round-off (a product of two bfloat16 numbers is
    exact in float32), and a float32 q split in parts does as well."""
    lay = case.layout
    rng = np.random.RandomState(3)
    keys = 4 * PAGE
    k = jnp.asarray(rng.randn(lay.heads, keys, lay.lanes), BF16)
    for q in (jnp.asarray(rng.randn(lay.heads, lay.group, lay.lanes), BF16),
              jnp.asarray(rng.randn(lay.heads, lay.group, lay.lanes), F32)):
        parts = 1 if q.dtype == BF16 else A.PARTS
        got = np.asarray(A._scores(
            A._operand(q.astype(F32), BF16, parts), k, lay.group))
        want = np.einsum("hgd,hkd->hgk", np.asarray(q, np.float64),
                         np.asarray(k.astype(F32), np.float64))
        # |score| ~ 16 here: 2e-6 relative is a few float32 ulps of the
        # sum's 256 terms
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-5)
