"""Driver ``generate_ssm_moe``: ``generate_ssm`` for a model whose
layers are ONE part each and whose feed-forward layers are routed
experts held in part (Nemotron-H: ``paddle_tpu/models/nemotron_h.py``).
The load, the window, the record and so the readers are
``generate_hybrid.run``'s, called as it is with this file's ``verify``
in the place of its own, as ``generate_ssm`` does.

What differs from ``generate_ssm``, and why it could not be told to that
driver by data: the reference takes this model's geometry (the groups of
B and C, the router's width and top-k, the scale, the held range) and
hands back the routed layers' chosen sets; and ``correct`` holds the
logits by TWO limits on the rows' own relative RMS, over ALL the rows of
the check (four prompts x 17), each between two readings this comparison
itself produces on every run:

- the lower-QUARTILE row's (``logits_rel_rms_quartile_row``), for what
  moves EVERY row.  Below it a sound run: the bfloat16 rounding and the
  router's flips it causes (a row crosses 23 routed layers; the top-6
  set differs from the reference's in a quarter of the (row, layer)
  pairs, and a held expert entering or leaving a row's sum moves that
  row by percents), which the rows with few flips read least of: the
  quartile and not the median, over all the prompts' rows and not the
  worst prompt's, because a prompt's rows share the flips of the rows
  before them through the states.  Above it the reference in float8
  (``precision_below_factor``) and each ablation, by its stated factor,
  at the lower-quartile row of the ablations' prompt;
- the WORST row's (``logits_rel_rms_worst_row``), for what breaks SOME
  rows, which a quartile cannot see.  Below it the worst row of a sound
  run; above it the faults ``planted`` in the system's side of the
  comparison, by their stated factors: ``another_token`` (ONE of the 16
  steps is fed another id: that row alone is another row's) and
  ``null_entry`` (every step addresses the null state entry in place of
  the sequence's: the 16 stepped rows lose the prompt's state).

Nothing is held over all rows together: that number is what a row or two
of flips make it, and it stood above what the float8 reference reads
(REVIEW of PR 64); it is written down in the facts with every row's own
number.  What the two cannot see: a fault that moves the rows of ONE
prompt's program alone by less than the worst row's limit (PERF.md
section 7).  Beside the logits the state entry's own number, as
``generate_ssm`` holds it and for its reason (16 teacher-forced rows of
logits cannot see the state's precision): the FIRST Mamba-2 layer's
entry after prefill + 16 steps through ``ssd_step`` against the entry
ONE prefill of the same rows leaves, at the MEDIAN head
(``median_head`` says why not over all heads), under ``state_rel_rms``;
the reference's own state rounded to bfloat16 after every row has to
read over it by ``state_precision_factor``.
"""

import importlib
import time
from unittest import mock

import numpy as np

from perf.drivers import generate_hybrid
from perf.drivers.generate import _generate
from perf.drivers.generate_conv_hybrid import routed_sets
from perf.drivers.generate_ssm import through_one_prefill, through_the_cache


def row_readings(ref, got, want):
    """The relative RMS of each row (of each head, of a layer's states):
    the numbers every limit here is read from."""
    return [ref.rel_rms(g, w) for g, w in zip(got, want)]


def quartile_row(rows):
    """The lower-quartile row's: what a quarter of the rows read under."""
    return float(np.percentile(rows, 25))


def median_head(ref, got, want):
    """One layer's states ``(H, P, N)`` -> the MEDIAN head's relative
    RMS.  Not all heads': on seed 3000000133, at step 6 of 16 behind
    7,500 rows, every head's write of that ONE row moved, rank one a
    head (6.8e-4 over all heads at that step and 2.2e-4 after the last;
    3.0e-4 at the median head three steps on and 9.4e-5 after the last;
    5e-6 the step before; once in 30 seeds' 120 comparisons).  What fits
    is a bfloat16 rounding edge in that row's input crossed by one of
    the two programs and not by the other (it was not traced further);
    a state kept in bfloat16 moves every head for good (3.2e-3 or more
    at the median one)."""
    return float(np.median(row_readings(ref, got, want)))


def with_the_null_entry(model):
    """``model.decode`` with every slot's state entry replaced by the
    null one: the planted fault of a slot that addresses another
    sequence's entry (``StateEntryBlock.entries_of`` reads it from the
    table's column behind the page run)."""
    decode = model.decode

    def faulty(step, states, tables, lens):
        tables = tables.copy()
        tables[:, model.full_pages] = 0
        return decode(step, states, tables, lens)

    return mock.patch.object(model, "decode", faulty)


def readings(model, wl, traffic, seed):
    """Everything ``correct`` is decided from but the streams ->
    (facts, problems).  The module's docstring says what is held; every
    reading is written down in the facts, the rows' own numbers
    included, so that another quantile can be read from a run's
    record."""
    import jax.numpy as jnp

    tol = wl["verify"]
    ref = importlib.import_module(f"perf.reference.{tol['reference']}")
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    n, slots = int(tol["tokens"]), int(traffic["gen_slots"])
    quartile_limit = float(tol["logits_rel_rms_quartile_row"])
    row_limit = float(tol["logits_rel_rms_worst_row"])
    state_limit = float(tol["state_rel_rms"])
    block = model.block
    facts, problems = {}, []

    def reference(ids, rows, ablate=None, **also):
        return ref.forward(
            model.params, jnp.asarray(ids, jnp.int32),
            layer_types=block.layer_types, num_heads=model.heads,
            head_dim=block.head_dim, mamba_n_heads=block.mamba_n_heads,
            mamba_d_head=block.mamba_d_head,
            mamba_d_state=block.mamba_d_state,
            mamba_n_groups=block.mamba_n_groups, top_k=block.top_k,
            scale=block.scale, held=block.held, eps=block.eps,
            ablate=ablate, rows=rows, **also)

    def write_down(name, got, want):
        """-> the rows' relative RMS, written down with their lower
        quartile, median and maximum and the number over all rows
        together (the first and the third are held)."""
        rows = row_readings(ref, got, want)
        facts[f"{name}_rows"] = [float(f"{r:.4g}") for r in rows]
        facts[f"{name}_quartile_row"] = quartile_row(rows)
        facts[f"{name}_median_row"] = float(np.median(rows))
        facts[f"{name}_worst_row"] = max(rows)
        facts[name] = ref.rel_rms(got, want)
        return rows

    every_row, worst_state = [], 0.0
    for i, T in enumerate(tol["prompt_lens"]):
        t0 = time.perf_counter()
        prompt = rng.randint(2, model.vocab, int(T)).tolist()
        tokens = rng.randint(2, model.vocab, n).tolist()
        got, stepped = through_the_cache(model, prompt, tokens, slots)
        whole = through_one_prefill(model, prompt + tokens)
        state_rms = median_head(ref, stepped[0], whole[0])
        facts[f"state_rel_rms_first_layer_T{T}_{i}"] = state_rms
        facts[f"state_rel_rms_first_layer_all_heads_T{T}_{i}"] = \
            ref.rel_rms(stepped[0], whole[0])
        facts[f"state_rel_rms_all_layers_T{T}_{i}"] = ref.rel_rms(
            stepped, whole)
        worst_state = max(worst_state, state_rms)
        rows = list(range(T - 1, T + n))
        held = i == int(tol.get("ablation_prompt", 0))
        if held:
            want, want_states, masks = reference(
                prompt + tokens, rows, states=True, masks=True)
            # beside the limit, not held: bfloat16 operands move every
            # layer's inputs, so this reads the logits' order
            facts["state_rel_rms_to_reference"] = ref.rel_rms(
                stepped, want_states)
            differ = np.any(routed_sets(model, prompt + tokens)
                            != np.asarray(masks), axis=-1)   # (layers, T)
            facts["top_k_set_differs_share"] = float(differ.mean())
        else:
            want = reference(prompt + tokens, rows)
        every_row += write_down(f"logits_rel_rms_T{T}_{i}", got, want)
        if not held:
            facts[f"verify_seconds_T{T}_{i}"] = round(
                time.perf_counter() - t0, 1)
            continue
        variants = [(a, f"without_{a}", tol["ablation_factor"][a])
                    for a in tol.get("ablations", ())]
        if tol.get("precision_below"):
            # the reference in the precision below the configuration's
            # must come out as not correct, by its stated factor too
            variants.append((tol["precision_below"],
                             f"reference_in_{tol['precision_below']}",
                             float(tol["precision_below_factor"])))
        for ablate, name, factor in variants:
            quartile = quartile_row(write_down(
                f"logits_rel_rms_{name}", got,
                reference(prompt + tokens, rows, ablate)))
            if quartile <= factor * quartile_limit:
                problems.append(
                    f"the quartile row's limit {quartile_limit} would not "
                    f"catch {name} by {factor}x: {quartile:.3e}")
        for ablate in tol.get("reported", ()):
            write_down(f"logits_rel_rms_without_{ablate}", got,
                       reference(prompt + tokens, rows, ablate))
        # the faults that break SOME rows, planted in the system's side
        # of this same comparison: each must read over the worst row's
        # limit by its stated factor
        planted = {}
        if "another_token" in tol["planted"]:
            fed = list(tokens)
            fed[n // 2] = 2 + (fed[n // 2] - 1) % (model.vocab - 2)
            planted["another_token"] = through_the_cache(
                model, prompt, fed, slots)[0]
        if "null_entry" in tol["planted"]:
            with with_the_null_entry(model):
                planted["null_entry"] = through_the_cache(
                    model, prompt, tokens, slots)[0]
        for name, factor in tol["planted"].items():
            row = max(write_down(f"logits_rel_rms_planted_{name}",
                                 planted[name], want))
            if row <= factor * row_limit:
                problems.append(
                    f"the worst row's limit {row_limit} would not catch "
                    f"the planted {name} by {factor}x: {row:.3e}")
        # the state in the precision below the configuration's float32
        _, low_states = reference(prompt + tokens, rows,
                                  tol["state_precision_below"], states=True)
        rms = median_head(ref, low_states[0], want_states[0])
        facts["state_rel_rms_first_layer_reference_state_in_bf16"] = rms
        facts["state_rel_rms_first_layer_all_heads_reference_state_in_bf16"] \
            = ref.rel_rms(low_states[0], want_states[0])
        facts["state_rel_rms_all_layers_reference_state_in_bf16"] = \
            ref.rel_rms(low_states, want_states)
        factor = float(tol["state_precision_factor"])
        if rms <= factor * state_limit:
            problems.append(
                f"the limit {state_limit} on the first layer's state would "
                f"not catch a bfloat16 state by {factor}x at the median "
                f"head: {rms:.3e}")
        facts[f"verify_seconds_T{T}_{i}"] = round(
            time.perf_counter() - t0, 1)
    quartile, worst_row = quartile_row(every_row), max(every_row)
    facts["logits_rel_rms_quartile_row"] = quartile
    facts["logits_rel_rms_worst_row"] = worst_row
    facts["state_rel_rms_first_layer_worst"] = worst_state
    if not quartile <= quartile_limit:
        problems.append(f"logits relative RMS of the quartile row "
                        f"{quartile:.3e} > {quartile_limit}")
    if not worst_row <= row_limit:
        problems.append(f"logits relative RMS of the worst row "
                        f"{worst_row:.3e} > {row_limit}")
    if not worst_state <= state_limit:
        problems.append("the first Mamba-2 layer's state entry: relative "
                        f"RMS of the median head {worst_state:.3e} > "
                        f"{state_limit}")
    return facts, problems


def verify(model, address, wl, traffic, seed, say):
    """``readings``, then /generate streams that must end at their
    count."""
    tol = wl["verify"]
    facts, problems = readings(model, wl, traffic, seed)
    rng = np.random.RandomState((seed + 1) % (2 ** 31 - 1))
    n = int(tol["tokens"])
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
