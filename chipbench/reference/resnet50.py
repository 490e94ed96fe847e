"""ResNet-50 (He et al., "Deep Residual Learning for Image Recognition",
arXiv:1512.03385, Table 1 and Figure 5 right), forward pass in plain
float32.

conv1 7x7/2 64 -> 3x3/2 pool -> four stages of [3, 4, 6, 3] bottleneck
blocks (1x1 w, 3x3 w, 1x1 4w; w = 64, 128, 256, 512) -> global average
pool -> 1000-way fully connected layer. Every convolution is followed by
batch normalisation (section 3.4); a projection shortcut (option B, 1x1
convolution + batch norm) is used where the shape changes, identity
elsewhere; the stride of a down-sampling block sits on its first 1x1
convolution, as in the paper's released model.

Departure from the paper, followed because the system under test and the
reference framework's `benchmark/fluid/models/resnet.py` do the same: the
pool after conv1 is a 3x3/2 AVERAGE pool without padding (112 -> 55), where
the paper has a max pool (112 -> 56). The stages therefore run at 55, 28,
14 and 7 pixels.

Tape order: conv1 filter, its batch norm (scale, bias, moving mean, moving
variance); then per block conv a, bn a, conv b, bn b, conv c, bn c and, for
a projection block, the shortcut's conv and bn; last the head's weight
[2048, classes] and bias.
"""

import jax
import jax.numpy as jnp

from . import convnet as cn


def _blocks(cfg):
    cin = cfg["stem_width"]
    for stage, (count, width) in enumerate(
            zip(cfg["blocks"], cfg["widths"])):
        for i in range(count):
            stride = 2 if (i == 0 and stage > 0) else 1
            cout = width * cfg["expansion"]
            yield cin, width, cout, stride, cin != cout
            cin = cout


def network(cfg, tape, x, train):
    x = cn.conv_bn(x, tape, 2, 3, train)
    x = cn.avg_pool(x, 3, 2)
    for cin, width, cout, stride, project in _blocks(cfg):
        y = cn.conv_bn(x, tape, stride, 0, train)
        y = cn.conv_bn(y, tape, 1, 1, train)
        y = cn.conv_bn(y, tape, 1, 0, train, relu=False)
        short = (cn.conv_bn(x, tape, stride, 0, train, relu=False)
                 if project else x)
        x = jax.nn.relu(short + y)
    x = jnp.mean(x, axis=(1, 2))
    return cn.dense(x, tape)


def layer_plan(cfg):
    """Every convolution and fully connected layer in tape order, with the
    shapes the cost functions need."""
    size = cn.conv_out(cfg["image_size"], 7, 2, 3)
    plan = [cn.conv_entry(3, cfg["stem_width"], 7, 2, 1, size, first=True)]
    size = cn.conv_out(size, 3, 2, 0)
    for cin, width, cout, stride, project in _blocks(cfg):
        out = cn.conv_out(size, 1, stride, 0)
        plan.append(cn.conv_entry(cin, width, 1, stride, 1, out))
        plan.append(cn.conv_entry(width, width, 3, 1, 1, out))
        plan.append(cn.conv_entry(width, cout, 1, 1, 1, out))
        if project:
            plan.append(cn.conv_entry(cin, cout, 1, stride, 1, out))
        size = out
    plan.append(cn.dense_entry(cfg["widths"][-1] * cfg["expansion"],
                               cfg["num_classes"]))
    return plan
