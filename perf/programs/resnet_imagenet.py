"""ResNet-50 v1 for ImageNet through ``paddle_tpu.models.resnet_imagenet``
(the builder ``bench.py:build`` uses), Momentum, cross-entropy."""


def build(cfg, traffic):
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet_imagenet

    fluid.framework.reset_default_programs()
    img = fluid.layers.data(name="img", shape=list(cfg["image"]),
                            dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    pred = resnet_imagenet(img, class_dim=cfg["class_dim"],
                           depth=cfg["depth"])
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=pred, label=label))
    main = fluid.default_main_program()
    forward = main.clone(for_test=True)   # forward ops only: FLOP count
    opt = cfg["optimizer"]
    fluid.optimizer.Momentum(learning_rate=opt["learning_rate"],
                             momentum=opt["momentum"]).minimize(loss)
    batch = int(traffic["batch"])
    return {
        "loss": loss, "main": main,
        "startup": fluid.default_startup_program(), "forward": forward,
        "feeds": {"img": {"shape": [batch] + list(cfg["image"]),
                          "draw": "normal"},
                  "label": {"shape": [batch, 1], "draw": "randint",
                            "high": cfg["class_dim"]}},
        "batch": batch, "work_per_step": batch, "work_unit": "img",
        "watch": "fc_0.w_0",
    }


def forward_flops_per_step(cfg, traffic, built):
    """Counted from the program's static shapes, per step."""
    from perf.harness.flops import program_flops

    return program_flops(built["forward"], batch_hint=built["batch"])
