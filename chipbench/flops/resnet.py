"""Operations of the ResNet-50 training step.

The forward pass at 224x224 is 4.09 G multiply-accumulates (the torchvision
/ fvcore count), two operations each, and a training step is three
forwards' worth (``bench.py`` has the same constant as
``RESNET50_TRAIN_FLOPS_PER_IMAGE``; nothing here reads it).
"""

RESNET50_FORWARD_MACS_224 = 4.09e9


def train_flops_per_example(config, traffic):
    if (config["depth"], config["image_size"]) != (50, 224):
        raise ValueError("only ResNet-50 at 224x224 has a counted constant")
    return 3 * 2 * RESNET50_FORWARD_MACS_224
