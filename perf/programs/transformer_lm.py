"""The GPT-2-shape LM through ``paddle_tpu.models.transformer_lm_loss``
(the builder ``chip_smoke.py`` and ``benchmark/transformer_bench.py``
use), Adam, bf16 AMP, flash attention under the ``auto`` rule."""

import numpy as np


def build(cfg, traffic):
    import paddle_tpu as fluid
    from paddle_tpu import models

    t = cfg["train"]
    S, B = int(traffic["seq"]), int(traffic["batch"])
    if B != t["batch"]:
        raise SystemExit("perf: the traffic's batch is not the batch the "
                         "configuration's file was sized for")
    fluid.framework.reset_default_programs()
    tokens = fluid.layers.data(name="tokens", shape=[S, 1], dtype="int64")
    labels = fluid.layers.data(name="labels", shape=[S, 1], dtype="int64")
    loss = models.transformer_lm_loss(
        tokens, labels=labels, vocab_size=cfg["vocab_size"],
        d_model=cfg["n_embd"], num_heads=cfg["n_head"],
        num_layers=t["n_layer"], ffn_mult=cfg["n_inner"] // cfg["n_embd"],
        recompute=bool(t["recompute"]))
    main = fluid.default_main_program()
    forward = main.clone(for_test=True)
    fluid.optimizer.Adam(
        learning_rate=cfg["optimizer"]["learning_rate"]).minimize(loss)
    draw = {"shape": [B, S, 1], "draw": "randint",
            "high": cfg["vocab_size"]}
    return {
        "loss": loss, "main": main,
        "startup": fluid.default_startup_program(), "forward": forward,
        "feeds": {"tokens": dict(draw), "labels": dict(draw)},
        "batch": B, "work_per_step": B * S, "work_unit": "tokens",
        "watch": None,
    }


def forward_flops_per_step(cfg, traffic, built):
    from perf.harness.flops import lm_forward_flops_per_token

    return built["work_per_step"] * lm_forward_flops_per_token(
        cfg["n_embd"], cfg["n_inner"], cfg["train"]["n_layer"],
        cfg["vocab_size"], int(traffic["seq"]))


def verify_reference_logits(env):
    """The first sequence through the executor (a test-mode clone of
    the program under bf16 AMP, the run's own seeded weights) against
    the plain float32 reference: logits by relative RMS, the loss by
    relative difference.  Returns the facts; raises AssertionError
    outside the tolerances written in the cell's file."""
    import jax
    import jax.numpy as jnp

    from perf.harness import runtime
    from perf.reference import gpt2_block as ref

    cfg, built, tol = env["config"], env["built"], env["workload"]["verify"]
    fwd = built["forward"]
    (sx,) = [op for op in fwd.global_block().ops
             if op.type == "softmax_with_cross_entropy"]
    logits_name = sx.input("Logits")[0]
    feed = {k: v[:1] for k, v in env["ring"][0].items()}
    got_loss, got = env["exe"].run(
        fwd, feed=feed, fetch_list=[built["loss"].name, logits_name],
        scope=env["scope"], return_numpy=False)
    got = jnp.asarray(got, jnp.float32)
    toks = jnp.asarray(feed["tokens"]).reshape(-1).astype(jnp.int32)
    labs = jnp.asarray(feed["labels"]).reshape(-1).astype(jnp.int32)
    params = ref.from_training_scope(env["scope"].values,
                                     cfg["train"]["n_layer"])
    act = ref.ACTIVATIONS[cfg["train"]["activation"]]
    run = jax.jit(ref.forward, static_argnums=(2, 3, 4))
    want = run(params, toks, cfg["n_head"], act, None)
    facts, problems = ref.compare(
        got, lambda ablate: (want if ablate is None else run(
            params, toks, cfg["n_head"], act, ablate)),
        tol, "logits_rel_rms")
    want_loss = float(ref.loss(want, labs))
    facts.update(loss=float(np.asarray(got_loss)), reference_loss=want_loss)
    facts["loss_rel"] = abs(facts["loss"] - want_loss) / abs(want_loss)
    if facts["loss_rel"] > tol["loss_rel"]:
        problems.append(f"loss_rel {facts['loss_rel']:.3e} > "
                        f"{tol['loss_rel']}")
    runtime.say(f"reference check: {facts}")
    assert not problems, "; ".join(problems)
    return facts
