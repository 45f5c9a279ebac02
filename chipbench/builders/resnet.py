"""ResNet through the program's public API: ``models/resnet.py`` ``build``
(ImageNet shape, NCHW, 7x7 stem, Nesterov momentum, bf16 AMP)."""

import numpy as np


def build(config, program, traffic, seed):
    """Returns ``(startup, step_program, loss, main)``."""
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    with fluid.unique_name.guard():
        main, startup, _, loss, _ = resnet.build(
            dataset="imagenet", depth=config["depth"],
            class_dim=config["num_classes"],
            batch_lr=config["training"]["learning_rate"], amp=True,
            data_format=config["data_format"], stem=config["stem"])
    main.random_seed = startup.random_seed = seed
    step = main
    if program.get("data_parallel"):
        step = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
    return startup, step, loss, main


def build_eval(config, program, traffic):
    """The network alone in test mode (batch norm on its moving statistics)
    under the same AMP rewrite, sharing the training program's weights by
    name: what the plain reference is compared with.  Its startup program
    is never run."""
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    size = config["image_size"]
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            img = fluid.layers.data(
                "img", shape=[config["image_channels"], size, size],
                dtype="float32")
            logits = resnet.resnet_imagenet(
                img, config["num_classes"], config["depth"], True,
                config["data_format"], config["stem"])
            fluid.contrib.mixed_precision.rewrite_program_bf16(main)
    out = {"feeds": ["img"], "fetch": {"logits": logits.name},
           "weights": [v.name for v in main.list_vars() if v.persistable]}
    if program.get("data_parallel"):
        main = fluid.CompiledProgram(main).with_data_parallel()
    return dict(out, program=main)


def make_pools(config, traffic, rng):
    """``ring`` pools of ``batch + offsets - 1`` float32 images: one draw of
    noise shared by the pools, plus each image's class template (7x7,
    upsampled), so that the label can be learned from the image."""
    size, chans = config["image_size"], config["image_channels"]
    classes = config["num_classes"]
    rows = traffic["batch"] + traffic["offsets"] - 1
    noise = traffic["noise_scale"] * rng.standard_normal(
        (rows, chans, size, size), dtype="float32")
    templates = rng.standard_normal((classes, chans, 7, 7), dtype="float32")
    up = size // 7
    noise = noise.reshape(rows, chans, 7, up, 7, up)
    pools = []
    for _ in range(traffic["ring"]):
        label = rng.integers(0, classes, (rows, 1)).astype("int64")
        img = noise + templates[label[:, 0]][:, :, :, None, :, None]
        pools.append({"img": img.reshape(rows, chans, size, size),
                      "label": label})
    return pools
