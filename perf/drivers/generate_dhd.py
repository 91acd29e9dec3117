"""Driver ``generate_dhd``: ``generate_ssm`` for a decoder-hybrid-decoder
(``paddle_tpu/models/phi4_flash.py``: Mamba-1 layers on a state entry,
window layers on rings, ONE full layer's page run that the cross layers
read, gated memory units).  The load, the window, the record and so the
readers are ``generate_hybrid.run``'s, called as it is with this file's
``verify`` and ``compiled_texts`` in the place of its own.

What differs, and why it could not be told to those drivers by data: the
reference takes this model's geometry (heads, head size, window; which
layer is which it reckons itself) and hands back the Mamba layers'
states as published, ``(C, N)`` a layer, where an entry stores them
``(N, C)``; and a bucket's prefill program is handed one flat row a
bucket row for EVERY layer that owns K/V (the eight rings and the run),
not the run's alone.

``correct`` holds what the timed programs produce at the timed shapes:
each seeded prompt prefilled through its bucket's program (layers 18-31
on the last row alone) and 16 seeded tokens teacher-forced at the
64-slot step's shape, all 17 logits rows against the reference's full
forward (every layer on every row); every ablation by its stated factor
against the prompt that wraps the rings; the reference with its weights
rounded to float8 over the limit; and, as ``generate_ssm`` says why, the
FIRST Mamba layer's state entry after prefill + 16 steps against ONE
prefill's, with the reference's bfloat16-state reading over that limit
by its factor.
"""

import importlib
from unittest import mock

import numpy as np

from perf.drivers import generate_hybrid, generate_ssm
from perf.drivers.generate import _generate
from perf.harness import runtime


def entry_states(model, pages):
    """The states of the sequence that holds ``pages``, as published:
    (Mamba layers, C, N) float32, on the host."""
    entry = model.allocator.entry_of(pages)
    return np.swapaxes(np.asarray(model.state_pool[:, entry]), 1, 2)


def compiled_texts(model, slots, ladder):
    """``generate_hybrid.compiled_texts`` with a prefill's addresses as
    this model has them: a flat row a bucket row for every layer that
    owns K/V, and the state entry."""
    from paddle_tpu.decode import model as dm

    cache = model._cache()
    step = dm._decode_step.lower(
        model.params, *cache[:2],
        np.zeros((slots, model.pages_per_seq), np.int32),
        np.zeros((slots,), np.int32), np.zeros((slots,), np.int32),
        heads=model.heads, page_size=model.page_size, block=model.block,
        extra=cache[2:]).compile()
    texts = {"decode_step": step.as_text()}
    planned = runtime.planned_bytes(step)
    for b in ladder:
        prefill = dm._prefill_bucket.lower(
            model.params, *cache[:2], np.zeros((b,), np.int32),
            (np.zeros((model.rings + 1, b), np.int32), np.int32(0)),
            np.int32(1), heads=model.heads, block=model.block,
            extra=cache[2:]).compile()
        texts[f"prefill_bucket_{b}"] = prefill.as_text()
        planned = max(planned, runtime.planned_bytes(prefill))
    return texts, planned


def verify(model, address, wl, traffic, seed, say):
    """As the module's docstring says -> (correct, facts)."""
    import jax.numpy as jnp

    tol = wl["verify"]
    ref = importlib.import_module(f"perf.reference.{tol['reference']}")
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    n, slots = int(tol["tokens"]), int(traffic["gen_slots"])
    limit = float(tol["logits_rel_rms"])
    state_limit = float(tol["state_rel_rms"])
    block = model.block
    facts, problems = {}, []
    # what a cached row of the run costs, as stored: both pools' bytes
    # over the rows they hold (no layer but the owner has a column)
    facts["run_row_bytes_stored"] = (
        2.0 * model.k_pool.nbytes
        / (model.allocator.num_pages * model.page_size))

    def reference(ids, rows, ablate=None, states=False):
        return ref.forward(
            model.params, jnp.asarray(ids, jnp.int32),
            num_heads=model.heads, head_dim=block.head_dim,
            window=block.window, eps=block.eps, ablate=ablate, rows=rows,
            states=states)

    worst = worst_state = 0.0
    for i, T in enumerate(tol["prompt_lens"]):
        prompt = rng.randint(2, model.vocab, int(T)).tolist()
        tokens = rng.randint(2, model.vocab, n).tolist()
        got, stepped = generate_ssm.through_the_cache(
            model, prompt, tokens, slots)
        whole = generate_ssm.through_one_prefill(model, prompt + tokens)
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
        problems.append("the first Mamba layer's state entry: relative RMS "
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
    with mock.patch.object(generate_ssm, "entry_states", entry_states), \
            mock.patch.object(generate_hybrid, "compiled_texts",
                              compiled_texts), \
            mock.patch.object(generate_hybrid, "verify", verify):
        return generate_hybrid.run(ctx)
