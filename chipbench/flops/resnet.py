"""Operations of the ResNet-50 training step.

``bench.py``'s ``RESNET50_TRAIN_FLOPS_PER_IMAGE``, copied: the forward pass
at 224x224 is 4.09 G multiply-accumulates (the torchvision / fvcore count),
two operations each, and a training step is three forwards' worth.
"""

RESNET50_FORWARD_MACS_224 = 4.09e9


def train_flops_per_example(config, traffic):
    if (config["depth"], config["image_size"]) != (50, 224):
        raise ValueError("only ResNet-50 at 224x224 has a counted constant")
    return 3 * 2 * RESNET50_FORWARD_MACS_224
