"""Operations and bytes, computed from shapes.

``program_flops`` / ``assert_model_flops`` are copied from
``benchmark/flops.py`` (sound, and the guard the benchmark wants: a
cell that does less work than its published cost is not ``correct``).
The language-model arithmetic redoes ``benchmark/transformer_bench.py``'s
6*params*tokens idea with the attention term it leaves out.
Recomputed operations never count: these are the operations the
algorithm needs, so a share of peak computed from them is a model
FLOP/s utilisation, not a hardware one.
"""


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


def program_flops(prog, batch_hint=1):
    """Forward matmul/conv FLOPs of a Program from its static var
    shapes (2*M*N*K per matmul; elementwise and norm traffic excluded).
    ``batch_hint`` stands in for a symbolic (-1/None) batch dimension."""
    block = prog.global_block()
    total = 0.0

    def dims(shape, hint):
        return [int(d) if d and d > 0 else hint for d in shape]

    for op in block.ops:
        t = op.type
        if t in ("conv2d", "conv2d_cudnn", "conv2d_transpose"):
            w = block.var(op.input("Filter")[0])
            ow = dims(block.var(op.output("Output")[0]).shape, batch_hint)
            n = ow[0] if len(ow) == 4 else 1
            k, cpg, kh, kw = [int(d) for d in w.shape]
            total += 2.0 * n * k * cpg * kh * kw * _prod(ow[-2:])
        elif t == "mul":
            xs = dims(block.var(op.input("X")[0]).shape, batch_hint)
            ys = [int(d) for d in block.var(op.input("Y")[0]).shape]
            ncol = int(op.attr("x_num_col_dims") or 1)
            total += (2.0 * (_prod(xs[:ncol]) or 1) * _prod(xs[ncol:])
                      * _prod(d for d in ys[1:] if d > 0))
        elif t == "matmul":
            xs = dims(block.var(op.input("X")[0]).shape, batch_hint)
            ys = [int(d) for d in block.var(op.input("Y")[0]).shape]
            n = ys[-1] if ys[-1] > 0 else batch_hint
            total += 2.0 * (_prod(xs[:-2]) or 1) * xs[-2] * xs[-1] * n
    return total


def assert_model_flops(got_gflop, want_gflop, rtol, what):
    """Fail loudly when the program's forward work diverges from the
    architecture's published cost."""
    if not want_gflop * (1 - rtol) <= got_gflop <= want_gflop * (1 + rtol):
        raise AssertionError(
            f"{what}: the program does {got_gflop:.3f} GFLOP forward vs "
            f"the published ~{want_gflop} (tolerance {rtol:.0%}): the "
            "graph does the wrong amount of work")
    return got_gflop


# -- GPT-2-shape language model ------------------------------------------


def lm_forward_flops_per_token(d_model, n_inner, n_layer, vocab, seq):
    """Forward FLOPs one token needs: the four attention projections
    (8 d^2) and the two feed-forward matmuls (4 d n_inner) per layer,
    causal attention (QK^T and PV over the (seq+1)/2 keys a position
    sees on average: 4 d (seq+1)/2 per layer) and the vocabulary head
    (2 d V).  Embedding lookups, norms and softmax are not matmuls."""
    per_layer = 8 * d_model ** 2 + 4 * d_model * n_inner
    attn = 4 * d_model * (seq + 1) / 2
    return n_layer * (per_layer + attn) + 2 * d_model * vocab


def lm_train_flops_per_token(d_model, n_inner, n_layer, vocab, seq):
    """Forward plus backward: the backward of a matmul is two matmuls."""
    return 3 * lm_forward_flops_per_token(d_model, n_inner, n_layer,
                                          vocab, seq)


def causal_attention_train_flops(batch, heads, seq, head_dim, n_layer):
    """FLOPs causal attention needs in one training step: forward two
    matmuls (QK^T, PV), backward four (dV, dP, dQ, dK), each
    2*S*S*D per head over the causal half.  A kernel's recomputation of
    the scores in its backward does not count."""
    one = 2.0 * seq * seq * head_dim * 0.5
    return 6 * one * batch * heads * n_layer


def kv_read_bytes(context_rows, heads, head_dim, n_layer, itemsize):
    """Bytes of K and V a decode step must read for ``context_rows``
    live tokens (summed over the sequences in the step)."""
    return 2.0 * context_rows * heads * head_dim * itemsize * n_layer
