"""Driver ``generate_ssm``: ``generate_hybrid`` for a model whose
recurrent layers are state-space layers (Mamba-2:
``paddle_tpu/models/granite_hybrid.py``).  The load, the window, the
record and so the readers are ``generate_hybrid.run``'s, called as it
is with this file's ``verify`` in the place of its own.

What differs, and why it could not be told to that driver by data: the
reference takes the state-space layers' geometry under its published
names (``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``), and
``correct`` holds a SECOND number beside the logits': the sequence's
state entry itself.  The logits cannot see the state's precision (16
teacher-forced rows of a random model read the same to two places with
the state rounded to bfloat16 after every row), and the state is what
this model's decode step mostly moves.

The entry as the prefill and then 16 steps leave it (written, read and
written again where it lies, by the step's kernel) is held to the entry
ONE prefill of the same rows leaves (the chunked recurrence, its state
float32 inside the program and stored once), in the FIRST mamba layer:
there both routes see the same inputs (the embedding's rows through one
norm and one projection) and the two entries agree to float32 rounding.
From the second layer on they do not: the last 16 rows differ by the
summation order of two programs, and every bfloat16 cast of an
activation turns a difference ``d`` into ``sqrt(d x ulp)``, 1e-3 by
the second layer and 2e-2 by the last (written down a layer, not
held).  One pool and one kernel serve all the layers, so the first
guards the precision of all.  Against the REFERENCE's final state the
entries read the logits' order for the same reason (bfloat16 operands
move every layer's inputs): written down, not held.  The reference's
final state with its state rounded to bfloat16 after every row, against
its own float32 one, has to read over the limit by its stated factor.
"""

import importlib
from unittest import mock

import numpy as np

from perf.drivers import generate_hybrid
from perf.drivers.generate import _generate


def entry_states(model, pages):
    """The states of the sequence that holds ``pages``, as published:
    (mamba layers, H, P, N) float32, on the host."""
    from paddle_tpu.models.granite_hybrid import unpack_state

    entry = model.allocator.entry_of(pages)
    return np.asarray(unpack_state(model.state_pool[:, entry],
                                   model.block.state_pack))


def through_the_cache(model, prompt, tokens, slots):
    """``generate_paged.through_the_cache`` (prefill, then ``tokens``
    teacher-forced, one decode step each, at the serving step's shape)
    -> (the len(tokens) + 1 logits rows, the sequence's states after
    the last token)."""
    pages = model.allocator.alloc(model.context_pages(prompt, len(tokens)))
    try:
        ctx, _, last = model.prefill(prompt, pages)
        rows = [np.asarray(last, np.float32)]
        slot = slots // 2
        tables = np.zeros((slots, model.pages_per_seq), np.int32)
        tables[slot] = model.pool_table(pages)
        lens = np.zeros((slots,), np.int32)
        lens[slot] = ctx
        for tok in tokens:
            step = np.full((slots, 1), model.bos_id, np.int64)
            step[slot, 0] = tok
            logits, _ = model.decode(step, [], tables, lens)
            lens[slot] += 1
            rows.append(np.asarray(logits[slot], np.float32))
        states = entry_states(model, pages)
    finally:
        model.allocator.free(pages)
    return np.stack(rows), states


def through_one_prefill(model, ids):
    """The states ONE prefill of ``ids`` leaves in the sequence's
    entry."""
    pages = model.allocator.alloc(model.context_pages(ids, 0))
    try:
        model.prefill(ids, pages)
        return entry_states(model, pages)
    finally:
        model.allocator.free(pages)


def verify(model, address, wl, traffic, seed, say):
    """``generate_hybrid.verify`` (logits of prefill + teacher-forced
    steps against the reference, each ablation by its stated factor,
    the reference in the precision below over the limit, /generate
    streams end at their count), and beside it the state entries, as
    the module's docstring says: ``state_rel_rms`` is the limit on the
    first mamba layer's."""
    import jax.numpy as jnp

    tol = wl["verify"]
    ref = importlib.import_module(f"perf.reference.{tol['reference']}")
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    n, slots = int(tol["tokens"]), int(traffic["gen_slots"])
    limit = float(tol["logits_rel_rms"])
    state_limit = float(tol["state_rel_rms"])
    block = model.block
    facts, problems = {}, []

    def reference(ids, rows, ablate=None, states=False):
        return ref.forward(
            model.params, jnp.asarray(ids, jnp.int32),
            layer_types=block.layer_types, num_heads=model.heads,
            head_dim=block.head_dim, mamba_n_heads=block.mamba_n_heads,
            mamba_d_head=block.mamba_d_head,
            mamba_d_state=block.mamba_d_state, eps=block.eps,
            ablate=ablate, rows=rows, states=states)

    worst = worst_state = 0.0
    for i, T in enumerate(tol["prompt_lens"]):
        prompt = rng.randint(2, model.vocab, int(T)).tolist()
        tokens = rng.randint(2, model.vocab, n).tolist()
        got, stepped = through_the_cache(model, prompt, tokens, slots)
        whole = through_one_prefill(model, prompt + tokens)
        rms = ref.rel_rms(stepped[0], whole[0])
        facts[f"state_rel_rms_first_layer_T{T}_{i}"] = rms
        facts[f"state_rel_rms_all_layers_T{T}_{i}"] = ref.rel_rms(
            stepped, whole)
        worst_state = max(worst_state, rms)
        rows = list(range(T - 1, T + n))
        held = i == int(tol.get("ablation_prompt", 0))
        want = reference(prompt + tokens, rows, states=held)
        if held:
            want, want_states = want
            # beside the limit, not held: bfloat16 operands move every
            # layer's inputs, so this reads the logits' order
            facts["state_rel_rms_to_reference"] = ref.rel_rms(
                stepped, want_states)
            facts["state_rel_rms_by_layer"] = [
                round(ref.rel_rms(a, b), 6) for a, b in zip(stepped, whole)]
        rms = ref.rel_rms(got, want)
        facts[f"logits_rel_rms_T{T}_{i}"] = rms
        facts[f"logits_rel_rms_T{T}_{i}_worst_row"] = max(
            ref.rel_rms(g, w) for g, w in zip(got, want))
        worst = max(worst, rms)
        if not held:
            continue
        variants = [(a, f"without_{a}", tol["ablation_factor"][a])
                    for a in tol.get("ablations", ())]
        if tol.get("precision_below"):
            # over the limit at all: the reference in the precision below
            # the configuration's must come out as not correct
            variants.append((tol["precision_below"],
                             f"reference_in_{tol['precision_below']}", 1.0))
        for ablate, name, factor in variants:
            rms = ref.rel_rms(got, reference(prompt + tokens, rows, ablate))
            facts[f"logits_rel_rms_{name}"] = rms
            if rms <= factor * limit:
                problems.append(f"the limit {limit} would not catch {name} "
                                f"by {factor}x: {rms:.3e}")
        # the state in the precision below the configuration's float32
        low, low_states = reference(prompt + tokens, rows,
                                    tol["state_precision_below"], True)
        facts["logits_rel_rms_reference_state_in_bf16"] = ref.rel_rms(
            got, low)
        rms = ref.rel_rms(low_states[0], want_states[0])
        facts["state_rel_rms_first_layer_reference_state_in_bf16"] = rms
        facts["state_rel_rms_all_layers_reference_state_in_bf16"] = \
            ref.rel_rms(low_states, want_states)
        factor = float(tol["state_precision_factor"])
        if rms <= factor * state_limit:
            problems.append(
                f"the limit {state_limit} on the first layer's state would "
                f"not catch a bfloat16 state by {factor}x: {rms:.3e}")
    facts["logits_rel_rms_worst"] = worst
    facts["state_rel_rms_first_layer_worst"] = worst_state
    if not worst <= limit:
        problems.append(f"logits relative RMS {worst:.3e} > {limit}")
    if not worst_state <= state_limit:
        problems.append("the first mamba layer's state entry: relative RMS "
                        f"{worst_state:.3e} > {state_limit}")
    for _ in range(int(tol["streams"])):
        p = rng.randint(2, model.vocab, int(tol["stream_prompt_len"])).tolist()
        ids = _generate(address, p, n)
        if len(ids) != n:
            problems.append(f"/generate gave {len(ids)} tokens of {n}")
    say(f"reference check: {facts}")
    for problem in problems:
        say(f"NOT CORRECT: {problem}")
    return not problems, facts


def run(ctx):
    with mock.patch.object(generate_hybrid, "verify", verify):
        return generate_hybrid.run(ctx)
