"""Plain float32 reference of the GPT-2-shape decoder (Cerebras-GPT).

Straightforward ``jax.numpy``: no kernel, no cache, no batching, every
matmul under ``jax.default_matmul_precision("highest")`` (on a TPU a
float32 matmul otherwise runs in bf16 passes).  It follows the
published block (Radford et al. 2019; Cerebras-GPT, arXiv:2304.03208):
learned positions, pre-LayerNorm, causal multi-head attention scaled by
1/sqrt(head_dim), a two-matmul feed-forward, a final LayerNorm and a
vocabulary head.

Departures from the published model, made because the CODE UNDER TEST
makes them (the benchmark may not change the program), each a
parameter here so the reference computes what the code claims to:

- ``activation``: the published model uses GELU.  The training LM
  (``paddle_tpu/models/transformer.py``) uses ReLU; the decode LM
  (``paddle_tpu/decode/model.py``) uses ``jax.nn.gelu`` in its default
  tanh approximation.
- biases: the published model has biases on every projection and on
  LayerNorm.  The training LM has none on q/k/v/proj; the decode LM has
  none anywhere and no LayerNorm bias.  A missing bias is ``None``.
- head: the published model ties the head to the token embedding.  The
  training LM has an untied ``lm_head``; pass it as ``head``.  The
  decode LM ties (``head=None``).
- LayerNorm epsilon 1e-5 in both programs, as published.

``ablate`` drops one piece of the mathematics ("scale" or "mask"); the
drivers use it once, when a cell is proved, to show that the written
tolerance would catch that omission.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def layer_norm(x, scale, bias=None, eps=1e-5):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    y = (x - m) * jax.lax.rsqrt(v + eps) * scale
    return y if bias is None else y + bias


def _linear(x, w, b=None):
    y = x @ w
    return y if b is None else y + b


def attention(q, k, v, num_heads, ablate=None):
    """Causal multi-head attention of one sequence: q, k, v (T, d)."""
    T, d = q.shape
    dh = d // num_heads
    q, k, v = (t.reshape(T, num_heads, dh).transpose(1, 0, 2)
               for t in (q, k, v))
    s = jnp.einsum("htd,hsd->hts", q, k)
    if ablate != "scale":
        s = s * dh ** -0.5
    if ablate != "mask":
        t = jnp.arange(T)
        s = jnp.where(t[:, None] >= t[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hts,hsd->htd", p, v).transpose(1, 0, 2).reshape(T, d)


def forward(params, tokens, num_heads, activation, ablate=None):
    """Logits (T, V) of one sequence of token ids (T,).

    ``params``: ``emb`` (V, d), ``pos`` (>=T, d), ``layers`` (a list of
    dicts with ``ln1_w ln1_b wq wk wv wo bo ln2_w ln2_b w1 b1 w2 b2``,
    biases possibly ``None``), ``lnf_w``, ``lnf_b``, ``head`` ((d, V)
    or ``None`` for the tied head)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), params)
        T = tokens.shape[0]
        x = p["emb"][tokens] + p["pos"][:T]
        for lp in p["layers"]:
            h = layer_norm(x, lp["ln1_w"], lp.get("ln1_b"))
            a = attention(h @ lp["wq"], h @ lp["wk"], h @ lp["wv"],
                          num_heads, ablate)
            x = x + _linear(a, lp["wo"], lp.get("bo"))
            h = layer_norm(x, lp["ln2_w"], lp.get("ln2_b"))
            x = x + _linear(activation(_linear(h, lp["w1"], lp.get("b1"))),
                            lp["w2"], lp.get("b2"))
        x = layer_norm(x, p["lnf_w"], p.get("lnf_b"))
        head = p.get("head")
        return x @ (p["emb"].T if head is None else head)


def loss(logits, labels):
    """Mean softmax cross-entropy of logits (T, V) against ids (T,)."""
    logp = jax.nn.log_softmax(logits.astype(F32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def rel_rms(a, b):
    """RMS of ``a - b`` over the RMS of ``b``."""
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b)))
                 / jnp.sqrt(jnp.mean(jnp.square(b))))


def compare(got, reference_of, tol, key):
    """``got`` against ``reference_of(None)`` by relative RMS, held to
    ``tol["logits_rel_rms"]``; and against ``reference_of(ablation)``
    for each of ``tol["ablations"]``, which must read over four times
    the tolerance (else the tolerance would not catch that omission).
    Returns (facts keyed ``key...``, the list of what failed)."""
    limit, problems = tol["logits_rel_rms"], []
    facts = {key: rel_rms(got, reference_of(None))}
    if facts[key] > limit:
        problems.append(f"{key} {facts[key]:.3e} > {limit}")
    for ablate in tol.get("ablations", ()):
        k = f"{key}_without_{ablate}"
        facts[k] = rel_rms(got, reference_of(ablate))
        if facts[k] <= 4 * limit:
            problems.append(f"the tolerance {limit} would not catch a "
                            f"missing {ablate} ({k} {facts[k]:.3e})")
    return facts, problems


ACTIVATIONS = {
    "relu": jax.nn.relu,
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
}


def from_training_scope(values, num_layers):
    """The training LM's parameters (``paddle_tpu/models/transformer.py``
    names) as ``forward``'s dict.  Its q/k/v projection is one packed
    (d, 3d) matrix split in thirds."""
    def g(name):
        return jnp.asarray(values[name], F32)

    layers = []
    for i in range(num_layers):
        wq, wk, wv = jnp.split(g(f"attn_{i}_qkv.w_0"), 3, axis=1)
        layers.append({
            "ln1_w": g(f"ln1_{i}.w_0"), "ln1_b": g(f"ln1_{i}.b_0"),
            "wq": wq, "wk": wk, "wv": wv, "wo": g(f"attn_{i}_proj.w_0"),
            "ln2_w": g(f"ln2_{i}.w_0"), "ln2_b": g(f"ln2_{i}.b_0"),
            "w1": g(f"ffn1_{i}.w_0"), "b1": g(f"ffn1_{i}.b_0"),
            "w2": g(f"ffn2_{i}.w_0"), "b2": g(f"ffn2_{i}.b_0")})
    return {"emb": g("tok_emb"), "pos": g("pos_emb"), "layers": layers,
            "lnf_w": g("ln_f.w_0"), "lnf_b": g("ln_f.b_0"),
            "head": g("lm_head.w_0")}


def from_decode_model(params):
    """The decode LM's parameters (``paddle_tpu/decode/model.py``) as
    ``forward``'s dict: no bias anywhere, tied head."""
    layers = [{"ln1_w": lp["ln1"], "wq": lp["wq"], "wk": lp["wk"],
               "wv": lp["wv"], "wo": lp["wo"], "ln2_w": lp["ln2"],
               "w1": lp["w1"], "w2": lp["w2"]} for lp in params["layers"]]
    return {"emb": params["emb"], "pos": params["pos"], "layers": layers,
            "lnf_w": params["ln_f"]}
