"""SE-ResNeXt-50 (32x4d) (Hu et al., "Squeeze-and-Excitation Networks",
arXiv:1709.01507, Table 1 right column; the ResNeXt block is Xie et al.,
arXiv:1611.05431, Figure 3c), forward pass in plain float32.

conv1 7x7/2 64 -> 3x3/2 max pool (padding 1) -> four stages of
[3, 4, 6, 3] blocks: 1x1 w, 3x3 w in 32 groups (the stride of a
down-sampling block sits here), 1x1 2w (w = 128, 256, 512, 1024), each
followed by batch norm; then squeeze (global average pool), excitation
(fc C/16 + ReLU, fc C + sigmoid) and channel-wise scaling; projection
shortcut (1x1 convolution + batch norm) where the shape changes ->
global average pool -> dropout 0.2 -> 1000-way fully connected layer.

Dropout: at inference the system scales by (1 - p) instead of dropping
(`downgrade_in_infer`, the reference framework's default); the reference
does the same. The training-mode gradient comparison runs with dropout 0
on both sides, because the mask comes from the system's own random stream
(`chipbench/configs/se_resnext50.py` sets the probability of the one
dropout op of the comparison program to 0; the timed program drops 0.2).

Tape order: conv1, bn; per block conv a, bn, conv b, bn, conv c, bn, SE fc1
weight and bias, SE fc2 weight and bias and, for a projection block, the
shortcut's conv and bn; last the head's weight and bias.
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
    g = cfg["cardinality"]
    x = cn.conv_bn(x, tape, 2, 3, train)
    x = cn.max_pool(x, 3, 2, 1)
    for cin, width, cout, stride, project in _blocks(cfg):
        y = cn.conv_bn(x, tape, 1, 0, train)
        y = cn.conv_bn(y, tape, stride, 1, train, groups=g)
        y = cn.conv_bn(y, tape, 1, 0, train, relu=False)
        s = jnp.mean(y, axis=(1, 2))
        s = jax.nn.relu(cn.dense(s, tape))
        s = jax.nn.sigmoid(cn.dense(s, tape))
        y = y * s[:, None, None, :]
        short = (cn.conv_bn(x, tape, stride, 0, train, relu=False)
                 if project else x)
        x = jax.nn.relu(short + y)
    x = jnp.mean(x, axis=(1, 2))
    if not train:
        x = x * (1.0 - cfg["dropout"])
    return cn.dense(x, tape)


def layer_plan(cfg):
    g, r = cfg["cardinality"], cfg["se_reduction"]
    size = cn.conv_out(cfg["image_size"], 7, 2, 3)
    plan = [cn.conv_entry(3, cfg["stem_width"], 7, 2, 1, size, first=True)]
    size = cn.conv_out(size, 3, 2, 1)
    for cin, width, cout, stride, project in _blocks(cfg):
        out = cn.conv_out(size, 3, stride, 1)
        plan.append(cn.conv_entry(cin, width, 1, 1, 1, size))
        plan.append(cn.conv_entry(width, width, 3, stride, g, out))
        plan.append(cn.conv_entry(width, cout, 1, 1, 1, out))
        plan.append(cn.dense_entry(cout, cout // r))
        plan.append(cn.dense_entry(cout // r, cout))
        if project:
            plan.append(cn.conv_entry(cin, cout, 1, stride, 1, out))
        size = out
    plan.append(cn.dense_entry(cfg["widths"][-1] * cfg["expansion"],
                               cfg["num_classes"]))
    return plan
