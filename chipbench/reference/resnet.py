"""The bottleneck ResNet's inference forward in plain ``jax.numpy``, float32.

The plain reference of the ``resnet*`` configurations (He et al. 2015, Table
1: 7x7/2 stem, 3x3/2 max pool, four stages of bottlenecks, global average
pool, one linear layer), batch norm with its moving statistics, NCHW.  It
shares no code with the program and takes the program's weights by the
names Fluid gives them: ``conv2d_<k>.w_0``, ``batch_norm_<k>.w_0|b_0|w_1|w_2``
(scale, bias, moving mean, moving variance), counted in the order
``models/resnet.py`` creates them (a block's shortcut first), and
``fc_0.w_0|b_0``.

Whether a block has a projection shortcut is read from the weights: the
next 1x1 filter is a projection exactly if it has the block's output width.
The paper projects only where the shape changes; ``models/resnet.py`` as it
stands projects in every block (PERF.md section 7), and this reference
follows whichever network the weights describe.
"""

import jax
import jax.numpy as jnp


def forward(w, feed, config):
    """``{"logits": [B, classes]}``."""
    count = [0]

    def conv_bn(x, stride, relu=True):
        k = count[0]
        count[0] += 1
        filt = w["conv2d_%d.w_0" % k]
        pad = filt.shape[-1] // 2
        x = jax.lax.conv_general_dilated(
            x, filt, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        stat = [w["batch_norm_%d.%s" % (k, n)][None, :, None, None]
                for n in ("w_0", "b_0", "w_1", "w_2")]
        x = (x - stat[2]) / jnp.sqrt(stat[3] + 1e-5) * stat[0] + stat[1]
        return jax.nn.relu(x) if relu else x

    def bottleneck(x, width, stride):
        short = x
        if w["conv2d_%d.w_0" % count[0]].shape[0] == 4 * width:
            short = conv_bn(x, stride, relu=False)
        y = conv_bn(x, stride)           # stride on the first 1x1, as built
        y = conv_bn(y, 1)
        y = conv_bn(y, 1, relu=False)
        return jax.nn.relu(short + y)

    x = conv_bn(feed["img"], 2)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    for stage, blocks in enumerate(config["stage_blocks"]):
        for block in range(blocks):
            x = bottleneck(x, config["stage_widths"][stage],
                           2 if stage and not block else 1)
    return {"logits": x.mean((2, 3)) @ w["fc_0.w_0"] + w["fc_0.b_0"]}
