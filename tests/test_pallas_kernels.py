"""Pallas kernel tests (interpret mode on CPU: numerics vs jnp, the
op-lowering integration path with the flag on, and the dispatch rules)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import pallas as pk
from paddle_tpu.pallas.softmax import softmax


@pytest.mark.parametrize("block_rows", [None, 64, 192],
                         ids=["default", "rows64", "invalid-falls-back"])
def test_softmax_kernel_numerics(rng, block_rows):
    """The tile is the default or the explicit argument; a block that
    does not divide the rows (192 of 512) falls back to the default."""
    from paddle_tpu.pallas import softmax as sm

    x = rng.randn(512, 256).astype("float32")
    got = np.asarray(softmax(jnp.asarray(x), block_rows, True))
    e = np.exp(x - x.max(-1, keepdims=True))
    np.testing.assert_allclose(got, e / e.sum(-1, keepdims=True), atol=1e-6)
    want = {None: sm.BLOCK_ROWS, 64: 64, 192: sm.BLOCK_ROWS}[block_rows]
    assert sm._resolve_block_rows(512, 256, block_rows) == want


def test_op_lowering_uses_pallas_and_trains(rng):
    """fc + softmax through the op path with pallas on (interpret): the
    softmax kernel runs in the lowered program, the forward matches the
    flag-off run and gradients still flow."""
    def build_and_run():
        fluid.framework.reset_default_programs()
        from paddle_tpu import executor as em

        em._global_scope = em.Scope()
        em._scope_stack = [em._global_scope]
        x = fluid.layers.data(name="x", shape=[512], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=256, bias_attr=False,
                            param_attr=fluid.param_attr.ParamAttr(
                                initializer=fluid.initializer.Constant(0.01)))
        sm = fluid.layers.softmax(h)
        loss = fluid.layers.mean(fluid.layers.cross_entropy(input=sm, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        xs = rng.randn(256, 512).astype("float32")
        ys = np.zeros((256, 1), "int64")
        (l1,) = exe.run(feed={"x": xs, "y": ys}, fetch_list=[loss])
        (l2,) = exe.run(feed={"x": xs, "y": ys}, fetch_list=[loss])
        return float(l1), float(l2)

    rng.seed(42)
    pk.enable(False)
    base = build_and_run()
    before = _dispatch_counts("softmax")["interpret"]
    try:
        pk.enable(True, interpret=True)
        rng.seed(42)
        with_pallas = build_and_run()
    finally:
        pk.enable("auto", interpret=False)
    assert _dispatch_counts("softmax")["interpret"] > before
    np.testing.assert_allclose(base[0], with_pallas[0], atol=1e-4)
    # loss decreased in both modes (grads flowed through custom vjp)
    assert with_pallas[1] < with_pallas[0]


def _lstm_scan_ref(xp, w, b, h0, c0):
    from jax import lax

    def step(carry, xt):
        h, c = carry
        gates = xt + h @ w + b
        i, f, g, o = jnp.split(gates, 4, -1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), (h, c)

    _, (hs, cs) = lax.scan(step, (h0, c0), xp)
    return hs, cs


def test_lstm_kernel_numerics_and_grad(rng):
    from paddle_tpu.pallas.lstm import lstm_seq

    T, B, H = 5, 8, 128
    xp = jnp.asarray(rng.randn(T, B, 4 * H).astype("float32")) * 0.5
    w = jnp.asarray(rng.randn(H, 4 * H).astype("float32")) * 0.1
    b = jnp.asarray(rng.randn(4 * H).astype("float32")) * 0.1
    h0 = jnp.asarray(rng.randn(B, H).astype("float32")) * 0.5
    c0 = jnp.asarray(rng.randn(B, H).astype("float32")) * 0.5

    hs_r, cs_r = _lstm_scan_ref(xp, w, b, h0, c0)
    hs_p, cs_p = lstm_seq(xp, w, b, h0, c0, True)
    np.testing.assert_allclose(np.asarray(hs_p), np.asarray(hs_r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(cs_p), np.asarray(cs_r), atol=1e-6)

    def loss(fn):
        def f(args):
            hs, cs = fn(*args)
            return jnp.sum(hs ** 2) + jnp.sum(cs[-1] ** 2)
        return f

    gr = jax.grad(loss(_lstm_scan_ref))((xp, w, b, h0, c0))
    gp = jax.grad(loss(lambda *a: lstm_seq(*a, True)))((xp, w, b, h0, c0))
    for a, p in zip(gr, gp):
        np.testing.assert_allclose(np.asarray(p), np.asarray(a),
                                   atol=5e-5, rtol=1e-4)


def test_lstm_op_pallas_path_matches_scan(rng):
    """The fused lstm op through the registry: pallas(interpret) output
    must equal the lax.scan lowering exactly."""
    def run_once():
        fluid.framework.reset_default_programs()
        from paddle_tpu import executor as em

        em._global_scope = em.Scope()
        em._scope_stack = [em._global_scope]
        B, T, H = 8, 6, 128
        xp = fluid.layers.data(name="xp", shape=[T, 4 * H], dtype="float32")
        hidden, cell = fluid.layers.dynamic_lstm(
            input=xp, size=H, use_peepholes=False)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        feed = {"xp": rng.randn(B, T, 4 * H).astype("float32") * 0.3}
        h, c = exe.run(feed=feed, fetch_list=[hidden, cell])
        return np.asarray(h), np.asarray(c)

    rng.seed(7)
    pk.enable(False)
    try:
        h_scan, c_scan = run_once()
        pk.enable(True, interpret=True)
        rng.seed(7)
        h_pal, c_pal = run_once()
    finally:
        pk.enable("auto", interpret=False)
    np.testing.assert_allclose(h_pal, h_scan, atol=1e-6)
    np.testing.assert_allclose(c_pal, c_scan, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention kernels (pallas/flash_attention.py)
# ---------------------------------------------------------------------------


def _attn_ref(q, k, v, causal):
    S, Sk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q, k) * (q.shape[-1] ** -0.5)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, Sk), bool))[None], s, -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)


def _qkv(rng, dtype):
    return [jnp.asarray(rng.randn(2, 256, 64).astype("float32")).astype(dtype)
            for _ in range(3)]


def _widened(ts):
    return [t.astype(jnp.float32) for t in ts]


# a bfloat16 result against a float32 reference on the same values: the
# output's own rounding (8 bits of mantissa) and no more than two of them
_BF16_TOL = 2.0 ** -7


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [None, (128, 128), (192, 128)],
                         ids=["default", "b128", "invalid-falls-back"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_fwd(rng, causal, blocks, dtype):
    """The blocks are ``_pick_block``'s or the explicit pair; a pair
    that does not divide S (192 of 256) falls back to the default.
    bfloat16 operands go to the products as stored: the output is the
    float32 reference's on the same values to the output's rounding, and
    ``lse`` (float32 out) is what the widened operands give: every
    product of two bfloat16 numbers is exact in float32."""
    from paddle_tpu.pallas import flash_attention as fa

    q, k, v = _qkv(rng, dtype)
    with jax.default_matmul_precision("highest"):
        if blocks is None:
            out, lse = fa.flash_attention_with_lse(q, k, v, causal, None,
                                                   True)
        else:
            out, lse = fa._flash_fwd_impl(q, k, v, causal, 64 ** -0.5, True,
                                          *blocks)
        ref = _attn_ref(*_widened((q, k, v)), causal)
        _, lse_wide = fa._flash_fwd_impl(*_widened((q, k, v)), causal,
                                         64 ** -0.5, True, *(blocks or ()))
    assert out.dtype == q.dtype and lse.dtype == jnp.float32
    tol = 1e-5 if dtype == "float32" else _BF16_TOL
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_wide),
                               atol=1e-5)
    want = (128, 128) if blocks == (128, 128) else (256, 256)
    assert fa._resolve_blocks(256, 256, 64, q.dtype.itemsize,
                              *(blocks or ())) == want


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(rng, causal, dtype):
    """bfloat16: the three gradients against the float32 reference's on
    the same values, to the gradients' own bfloat16 rounding."""
    from paddle_tpu.pallas.flash_attention import flash_attention

    q, k, v = _qkv(rng, dtype)

    with jax.default_matmul_precision("highest"):
        def loss_k(q, k, v):
            out = flash_attention(q, k, v, causal, None, True)
            return jnp.sum(jnp.cos(out.astype(jnp.float32)))

        def loss_r(q, k, v):
            return jnp.sum(jnp.cos(_attn_ref(q, k, v, causal)))

        got = jax.grad(loss_k, (0, 1, 2))(q, k, v)
        want = jax.grad(loss_r, (0, 1, 2))(*_widened((q, k, v)))
    for a, w in zip(got, want):
        assert a.dtype == q.dtype
        a, w = np.asarray(a, np.float32), np.asarray(w)
        if dtype == "float32":
            np.testing.assert_allclose(a, w, atol=2e-3, rtol=1e-3)
        else:
            # the forward's rounded output moves the cotangent too
            np.testing.assert_allclose(
                a, w, atol=2 * _BF16_TOL * np.abs(w).max(), rtol=_BF16_TOL)


@pytest.mark.parametrize("S, D, itemsize, want", [
    (2048, 128, 2, (1024, 1024)),    # the LM step: the sweep's pair
    (8192, 192, 2, (1024, 1024)),    # heads of 192 laid out at 256 lanes
    (1024, 128, 4, (1024, 1024)),    # a float32 1,024-row bucket: one block
    (1536, 128, 2, (512, 512)),      # 1,024 does not divide: the next one
    (4608, 128, 2, (512, 512)),      # K-EXAONE's top bucket, 9 x 512
    (1280, 128, 4, (256, 256)),      # Cerebras generate's capacity
    (2048, 256, 4, (512, 512)),      # float32 heads of 256: 1,024 is too much
    (2048, 256, 2, (1024, 1024)),    # ... the same heads in bfloat16 are not
    (384, 64, 2, (384, 384)),        # shorter than the preference: one block
    (1000, 64, 2, (1000, 1000)),
    (2056, 64, 2, (0, 0)),           # no divisor of 128 rows and up
], ids=lambda v: str(v).replace(" ", ""))
def test_flash_block_rule(S, D, itemsize, want):
    """``_resolve_blocks`` with no explicit pair: per kernel the largest
    power-of-two divisors up to ``BLOCK_PREF`` that ``_resident`` admits
    at the operands' itemsize; an explicit pair the model refuses falls
    back to it; ``fits`` is whether every kernel has one."""
    from paddle_tpu.pallas import flash_attention as fa

    assert fa.BLOCK_PREF == 1024
    for kernel in fa.KERNELS:
        assert fa._resolve_blocks(S, S, D, itemsize, kernel=kernel) == want
        assert fa._resolve_blocks(S, S, D, itemsize, 4096, 4096,
                                  kernel=kernel) == want
    assert fa.fits(1, 8, S, D) == bool(want[0])
    if want[0]:
        assert fa._pick_block(S) >= want[0]


# what Mosaic needs for one call, MiB: the least ``vmem_limit_bytes`` at
# which each kernel compiles for a described v5e, found by bisection to
# half a MiB (PERF.md §6, PR 46): D, itemsize, pair, fwd, dq, dkv
_VMEM_NEED = [
    (128, 2, (1024, 1024), 9.5, 7.0, 9.0),
    (128, 2, (512, 512), 3.0, 2.0, 3.0),
    (128, 2, (2048, 1024), 17.5, 13.5, 16.0),
    (128, 4, (1024, 1024), 11.5, 10.0, 10.5),
    (128, 4, (512, 1024), 7.0, 5.5, 7.0),
    (128, 4, (2048, 1024), 21.5, 19.0, 17.5),
    (256, 2, (1024, 1024), 12.5, 9.0, 12.0),
    (256, 2, (1024, 512), 9.0, 6.0, 6.0),
    (256, 4, (1024, 1024), 16.5, 16.5, 15.5),
    (256, 4, (512, 512), 7.0, 5.5, 5.5),
    (64, 2, (1024, 1024), 7.5, 6.0, 7.5),
]


@pytest.mark.parametrize("D, itemsize, pair, fwd, dq, dkv", _VMEM_NEED,
                         ids=lambda v: str(v).replace(" ", ""))
def test_flash_residency_bounds_what_the_compiler_needs(D, itemsize, pair,
                                                         fwd, dq, dkv):
    """``_resident`` counts the operands' itemsize and each kernel's own
    blocks: it is at or over what the compiler needed for every measured
    (shape, pair), so a pair it admits compiles, and it refuses nothing
    that needs under three quarters of the limit."""
    from paddle_tpu.pallas import flash_attention as fa

    mib = 2.0 ** 20
    for kernel, need in zip(fa.KERNELS, (fwd, dq, dkv)):
        model = fa._resident(kernel, *pair, D, itemsize)
        assert model > (need - 0.5) * mib, (kernel, model / mib)
        ok = fa._blocks_ok(2048, 2048, D, *pair, itemsize, kernel)
        assert not (ok and need > 16), kernel
        assert ok or need > 12, kernel
        if itemsize == 2:
            assert model < fa._resident(kernel, *pair, D, 4)


def test_flash_attention_via_attention_op(rng):
    """scaled_dot_product_attention lowers through the flash kernel with
    the flag on (interpret) and matches the flag-off jnp path."""
    def run():
        fluid.framework.reset_default_programs()
        from paddle_tpu import executor as em

        em._global_scope = em.Scope()
        em._scope_stack = [em._global_scope]
        x = fluid.layers.data(name="x", shape=[256, 64], dtype="float32")
        from paddle_tpu.layer_helper import LayerHelper

        h = LayerHelper("fa_test")
        out = h.create_tmp_variable("float32", x.shape)
        h.append_op(type="scaled_dot_product_attention",
                    inputs={"Q": [x], "K": [x], "V": [x]},
                    outputs={"Out": [out]}, attrs={"causal": True})
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        feed = {"x": rng.randn(2, 256, 1, 64).astype("float32")
                .reshape(2, 256, 64)[:, :, None, :].reshape(2, 256, 1, 64)}
        (o,) = exe.run(feed=feed, fetch_list=[out])
        return o

    rng_state = rng.get_state()
    pk.enable(False)
    want = run()
    rng.set_state(rng_state)
    pk.enable(True, interpret=True)
    try:
        got = run()
    finally:
        pk.enable("auto", interpret=False)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)


def test_ring_attention_flash_chunks_match_jnp(rng):
    """Ring attention with the flash kernel as the per-chunk block
    (interpret mode) must match both the jnp ring and the unsharded
    reference, forward and gradients, on a 4-way sp mesh."""
    import importlib

    from jax.sharding import Mesh

    ra = importlib.import_module("paddle_tpu.parallel.ring_attention")
    devs = np.array(jax.devices("cpu")[:4])
    mesh = Mesh(devs, ("sp",))
    B, H, S, D = 1, 2, 512, 64
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
               for _ in range(3))

    with jax.default_matmul_precision("highest"):
        ref = ra.local_attention(q, k, v, causal=True)

        def run(use_flash):
            if use_flash:
                pk.enable(True, interpret=True)
            else:
                pk.enable(False)
            try:
                return ra.ring_attention_sharded(mesh, "sp", q, k, v,
                                                 causal=True)
            finally:
                pk.enable("auto", interpret=False)

        np.testing.assert_allclose(np.asarray(run(True)), np.asarray(ref),
                                   atol=2e-5)

        def loss(t, use_flash):
            if use_flash:
                pk.enable(True, interpret=True)
            else:
                pk.enable(False)
            try:
                o = ra.ring_attention_sharded(mesh, "sp", *t, causal=True)
            finally:
                pk.enable("auto", interpret=False)
            return jnp.sum(jnp.cos(o))

        g_jnp = jax.grad(lambda t: loss(t, False))((q, k, v))
        g_fl = jax.grad(lambda t: loss(t, True))((q, k, v))
    for a, b in zip(g_jnp, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)



# (entries, H, d_v, d_k, the entry of each slot, what the rows are)
_DELTA_CASES = {
    "toy": (7, 4, 16, 6, (3, 5, 6), "random"),
    "published-block": (3, 2, 192, 96, (2, 1), "random"),
    "out-of-order": (9, 3, 8, 5, (8, 2, 7, 1, 4), "random"),
    "two-slots-on-entry-0": (6, 2, 16, 6, (0, 4, 0, 2), "random"),
    "g0-beta0": (5, 2, 16, 6, (4, 1), "padding"),
}


@pytest.mark.parametrize("case", _DELTA_CASES)
def test_gated_delta_step_is_the_step_on_the_gathered_entries(rng, case):
    """``gated_delta_step`` against ``step_gated_delta`` on the entries
    the slots address (keys stored at 128 lanes, zero beyond ``d_k``;
    beta in (0, 2)): ``o`` and the new entries within 1e-5, every entry
    no slot addresses bit-identical.  Slots on the null entry 0 write
    it in no order: it stays finite and the other slots' are right.
    A row with ``g = 0, beta = 0`` leaves its entry as it was, to the
    bit."""
    from paddle_tpu.models.olmo_hybrid import step_gated_delta
    from paddle_tpu.pallas import gated_delta as gd

    N, H, dv, dk, at, rows = _DELTA_CASES[case]
    S, wide = len(at), 128
    at = np.asarray(at, np.int32)

    def keys(*shape):
        x = np.zeros(shape + (wide,), np.float32)
        x[..., :dk] = rng.randn(*shape, dk)
        return x

    pool = keys(N, H, dv)
    q, k = keys(S, H), keys(S, H)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(S, H, dv).astype(np.float32)
    g = -rng.uniform(0.001, 0.3, (S, H)).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, (S, H)).astype(np.float32)
    if rows == "padding":
        g, beta = np.zeros_like(g), np.zeros_like(beta)
    assert gd.fits(pool.dtype, H, dv, wide)
    o, new = gd.gated_delta_step(jnp.asarray(pool), at, q, k, v, g, beta,
                                 interpret=True)
    want_o, want_new = step_gated_delta(q, k, v, g, beta, pool[at])
    o, new = np.asarray(o), np.asarray(new)
    live = at != 0
    np.testing.assert_allclose(o[live], np.asarray(want_o)[live], atol=1e-5)
    np.testing.assert_allclose(new[at[live]], np.asarray(want_new)[live],
                               atol=1e-5)
    untouched = np.setdiff1d(np.arange(N), at)
    np.testing.assert_array_equal(new[untouched], pool[untouched])
    assert np.isfinite(new[0]).all() and not new[..., dk:].any()
    if rows == "padding":
        np.testing.assert_array_equal(new, pool)


def test_gated_delta_step_fits_whole_tiles_of_float32():
    from paddle_tpu.pallas import gated_delta as gd

    assert gd.fits(jnp.float32, 30, 192, 128)
    assert gd.head_block(30, 192, 128) == 15
    assert gd.head_block(4, 16, 128) == 4
    assert not gd.fits(jnp.bfloat16, 30, 192, 128)       # a bf16 state
    assert not gd.fits(jnp.float32, 30, 192, 96)         # keys not padded
    assert not gd.fits(jnp.float32, 30, 12, 128)         # d_v off the tile


# ---------------------------------------------------------------------------
# which path runs: the rules of pallas/__init__.py and the sites that
# pass their own fits() (parallel/ring_attention.py, decode/attention.py)
# ---------------------------------------------------------------------------


def _dispatch_counts(kernel):
    return {p: pk._M_DISPATCH.value(kernel=kernel, path=p)
            for p in ("compiled", "interpret", "reference")}


def _decide(kernel, shape):
    """Run one dispatch decision of ``kernel`` at ``shape`` the way its
    caller does; the decision where the site returns one."""
    import importlib

    from paddle_tpu.decode import attention as da

    if kernel == "lstm":
        return pk.use_lstm(*shape)
    if kernel == "softmax":
        return pk.use_softmax(*shape)
    if kernel == "flash_attention":
        return pk.use_flash_attention(*shape)
    if kernel == "ring_flash_attention":
        ra = importlib.import_module("paddle_tpu.parallel.ring_attention")
        return ra._use_flash_chunks(*shape)
    if kernel.startswith("ragged_paged_attention"):
        return da._use_kernel(kernel, *shape)
    if kernel == "gated_delta_step":
        return pk.use_gated_delta_step(*shape)
    if kernel == "ssd_step":
        return pk.use_ssd_step(*shape)
    if kernel == "gated_delta_chunked":
        return pk.use_gated_delta_chunked(*shape)
    if kernel == "conv_step":
        dtype, taps, channels, entry = shape
        return pk.use_conv_step(dtype, entry, dtype, taps, channels)
    if kernel == "grouped_gemm":
        dtype, rows, d, f = shape
        return pk.use_grouped_gemm(dtype, dtype, rows, d, f)
    if kernel == "ring_paged_attention":
        heads_major, T, page, Hq, Hkv, D = shape
        S, R, N = 4, 5, 21
        pages = jax.ShapeDtypeStruct(
            (N, Hkv, page, D) if heads_major else (N, page, Hkv, D),
            jnp.bfloat16)
        jax.eval_shape(                                     # trace only
            lambda q, k, v, ring, pos: da.paged_ring_attention(
                q, k, v, ring, pos, 4 * page, heads_major=heads_major),
            jax.ShapeDtypeStruct((S, T, Hq, D), jnp.bfloat16), pages, pages,
            jax.ShapeDtypeStruct((S, R), jnp.int32),
            jax.ShapeDtypeStruct((S, T), jnp.int32))
        return None
    assert kernel == "prefill_flash_attention"
    x = jax.ShapeDtypeStruct(shape, jnp.float32)     # (T, H, D); trace only
    # a new function each time: eval_shape caches a function's trace
    jax.eval_shape(lambda q, k, v: da.dense_prefill_attention(q, k, v),
                   x, x, x)
    return None


# in: a shape the kernel's fits() accepts on the kernel's side of its
# threshold; out: one fits() accepts on the other side
_IN = {"lstm": (16, 384), "softmax": (1024, 256),
       "flash_attention": (8, 1024, 1024, 128),
       "ring_flash_attention": (1, 8, 1024, 128)}
_OUT = {"lstm": (16, 512), "softmax": (1024, 512),
        "flash_attention": (8, 512, 512, 128),
        "ring_flash_attention": (1, 8, 512, 128)}

_POLICY_CASES = (
    # auto on a TPU backend: each threshold, both sides
    [(k, _IN[k], "auto", True, False, "compiled") for k in _IN]
    + [(k, _OUT[k], "auto", True, False, "reference") for k in _OUT]
    # auto off a TPU: the reference, unless interpret mode is set, and
    # then the threshold decides as it does on the chip
    + [(k, _IN[k], "auto", False, False, "reference") for k in _IN]
    + [(k, _IN[k], "auto", False, True, "interpret") for k in _IN]
    + [(k, _OUT[k], "auto", False, True, "reference") for k in _OUT]
    # on: fits() alone decides; off: always the reference
    + [(k, _OUT[k], "on", True, False, "compiled") for k in _OUT]
    + [("lstm", (12, 512), "on", True, False, "reference"),
       ("softmax", (1024, 200), "on", True, False, "reference"),
       ("flash_attention", (8, 512, 256, 128), "on", True, False,
        "reference"),
       ("flash_attention", (8, 512, 512, 128), "on", False, True,
        "interpret")]
    + [(k, _IN[k], "off", True, False, "reference") for k in _IN]
    # the decode kernels have no threshold: wherever fits() holds
    + [("ragged_paged_attention", (16, 16, 128), "auto", True, False,
        "compiled"),
       ("ragged_paged_attention_chunk", (16, 16, 128), "auto", False, True,
        "interpret"),
       ("ragged_paged_attention", (16, 16, 128), "auto", False, False,
        "reference"),
       ("ragged_paged_attention", (12, 16, 128), "on", True, False,
        "reference"),
       ("ragged_paged_attention_chunk", (16, 16, 128), "off", True, False,
        "reference"),
       # a window layer's ring (pages heads-major?, chunk rows, page, Hq,
       # Hkv, D): the layout the caller states decides, nothing else:
       # Phi-4-mini-flash's pages, K-EXAONE's, a chunk, one over a page
       ("ring_paged_attention", (True, 1, 128, 40, 10, 128), "auto", True,
        False, "compiled"),
       ("ring_paged_attention", (False, 1, 128, 64, 8, 128), "auto", True,
        False, "reference"),
       ("ring_paged_attention", (False, 1, 128, 64, 8, 128), "on", True,
        False, "reference"),
       ("ring_paged_attention", (True, 4, 128, 40, 10, 128), "auto", True,
        False, "compiled"),
       ("ring_paged_attention", (True, 129, 128, 40, 10, 128), "on", True,
        False, "reference"),
       ("ring_paged_attention", (True, 1, 8, 4, 2, 8), "auto", False, True,
        "interpret"),
       ("ring_paged_attention", (True, 1, 128, 40, 10, 128), "auto", False,
        False, "reference"),
       ("ring_paged_attention", (True, 1, 128, 40, 10, 128), "off", True,
        False, "reference"),
       ("prefill_flash_attention", (128, 2, 8), "auto", True, False,
        "compiled"),
       ("prefill_flash_attention", (128, 2, 8), "auto", False, True,
        "interpret"),
       ("prefill_flash_attention", (64, 2, 8), "auto", True, False,
        "reference"),
       ("prefill_flash_attention", (128, 2, 8), "off", True, False,
        "reference"),
       ("gated_delta_step", ("float32", 30, 192, 128), "auto", True, False,
        "compiled"),
       ("gated_delta_step", ("float32", 4, 16, 128), "auto", False, True,
        "interpret"),
       ("gated_delta_step", ("float32", 30, 192, 128), "auto", False, False,
        "reference"),
       ("gated_delta_step", ("float32", 4, 10, 128), "on", True, False,
        "reference"),
       ("gated_delta_step", ("float32", 30, 192, 128), "off", True, False,
        "reference"),
       # a bucket's rows, then the state (heads, d_v, d_k)
       ("gated_delta_chunked", ("float32", 4096, 30, 192, 96), "auto", True,
        False, "compiled"),
       ("gated_delta_chunked", ("float32", 128, 30, 192, 96), "auto", True,
        False, "compiled"),
       ("gated_delta_chunked", ("float32", 128, 4, 16, 8), "auto", False,
        True, "interpret"),
       ("gated_delta_chunked", ("float32", 4096, 30, 192, 96), "auto", False,
        False, "reference"),
       ("gated_delta_chunked", ("float32", 64, 30, 192, 96), "on", True,
        False, "reference"),
       ("gated_delta_chunked", ("bfloat16", 4096, 30, 192, 96), "on", True,
        False, "reference"),
       ("gated_delta_chunked", ("float32", 4096, 30, 192, 96), "off", True,
        False, "reference"),
       # entries (rows of heads, state size, lanes)
       ("ssd_step", ("float32", 32, 128, 128), "auto", True, False,
        "compiled"),
       ("ssd_step", ("float32", 1, 128, 128), "auto", False, True,
        "interpret"),
       ("ssd_step", ("float32", 32, 128, 128), "auto", False, False,
        "reference"),
       ("ssd_step", ("float32", 64, 128, 64), "on", True, False,
        "reference"),
       ("ssd_step", ("float32", 32, 128, 128), "off", True, False,
        "reference"),
       # the tail pool's dtype, taps, channels, an entry as stored
       ("conv_step", ("bfloat16", 4, 4352, (102, 128)), "auto", True, False,
        "compiled"),
       ("conv_step", ("bfloat16", 4, 11520, (270, 128)), "auto", True,
        False, "compiled"),
       ("conv_step", ("float32", 4, 384, (9, 128)), "auto", False, True,
        "interpret"),
       ("conv_step", ("bfloat16", 4, 4352, (102, 128)), "auto", False,
        False, "reference"),
       ("conv_step", ("float32", 4, 112, (3, 112)), "on", True, False,
        "reference"),
       ("conv_step", ("bfloat16", 4, 4352, (13056,)), "on", True, False,
        "reference"),
       ("conv_step", ("bfloat16", 4, 4352, (102, 128)), "off", True, False,
        "reference"),
       # sorted rows of a block, then an expert's (d, f): the three MoE
       # cells' prefill blocks, a few-slot step, the tests' toy widths
       ("grouped_gemm", ("bfloat16", 2304, 6144, 2048), "auto", True, False,
        "compiled"),
       ("grouped_gemm", ("bfloat16", 4096, 2048, 1024), "auto", True, False,
        "compiled"),
       ("grouped_gemm", ("bfloat16", 1024, 2048, 768), "auto", True, False,
        "compiled"),
       ("grouped_gemm", ("bfloat16", 32, 6144, 2048), "auto", True, False,
        "reference"),
       ("grouped_gemm", ("float32", 640, 128, 128), "auto", False, True,
        "interpret"),
       ("grouped_gemm", ("bfloat16", 2304, 6144, 2048), "auto", False, False,
        "reference"),
       ("grouped_gemm", ("float32", 825, 16, 12), "on", True, False,
        "reference"),
       ("grouped_gemm", ("float32", 600, 128, 128), "on", True, False,
        "reference"),
       ("grouped_gemm", ("bfloat16", 2304, 6144, 2048), "off", True, False,
        "reference")])


@pytest.fixture
def pallas_state():
    saved = dict(pk._STATE)
    yield
    pk._STATE.update(saved)


@pytest.mark.parametrize(
    "kernel,shape,mode,on_tpu,interpret,path", _POLICY_CASES,
    ids=[f"{k}-{'x'.join(map(str, s))}-{m}-{'tpu' if t else 'cpu'}"
         f"{'-interpret' if i else ''}" for k, s, m, t, i, _ in _POLICY_CASES])
def test_kernel_policy(monkeypatch, pallas_state, kernel, shape, mode,
                       on_tpu, interpret, path):
    """(kernel, shape, mode, backend, interpret) -> the path that runs,
    and the ``pallas_dispatch_total{kernel, path}`` label it counts."""
    monkeypatch.setattr(pk, "tpu_backend", lambda: on_tpu)
    pk.enable(mode, interpret=interpret)
    before = _dispatch_counts(kernel)
    use = _decide(kernel, shape)
    after = _dispatch_counts(kernel)
    moved = {p: after[p] - before[p] for p in after if after[p] != before[p]}
    assert moved == {path: 1}
    assert use in (None, path != "reference")


def test_flash_threshold_is_read_from_one_place(monkeypatch, pallas_state):
    """Moving ``pallas.FLASH_MIN_SEQ`` moves the attention op's choice
    (``use_flash_attention``, all ``ops/attention_ops.py`` asks) and ring
    attention's together."""
    monkeypatch.setattr(pk, "tpu_backend", lambda: True)
    pk.enable("auto", interpret=False)
    op, ring = _OUT["flash_attention"], _OUT["ring_flash_attention"]
    assert pk.FLASH_MIN_SEQ == 1024
    assert not _decide("flash_attention", op)
    assert not _decide("ring_flash_attention", ring)
    monkeypatch.setattr(pk, "FLASH_MIN_SEQ", 512)
    assert _decide("flash_attention", op)
    assert _decide("ring_flash_attention", ring)
