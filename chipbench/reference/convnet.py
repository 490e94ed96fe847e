"""Plain float32 building blocks for the two reference networks, written
from the papers' layer equations in straightforward `jax.numpy` (the
convolution itself is `lax.conv_general_dilated`: no kernel, no mixed
precision, no fusion). Activations are NHWC, filters OIHW
([out, in / groups, k, k]), as the papers' tables give them.

Parameters travel as a TAPE: a flat list of float32 arrays that the network
function consumes front to back, in the order its `layer_plan` lists them.
That keeps the reference free of the system's variable names; the
configuration's builder (`chipbench/configs/<config>.py`) knows how to lay
the system's weights out on the tape.
"""

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5          # Ioffe & Szegedy 2015, as the system's default
BN_MOMENTUM = 0.9      # moving = 0.9 * moving + 0.1 * batch


class Tape:
    """Reads arrays off a list in order, and collects the batch-norm moving
    statistics a training pass would write back."""

    def __init__(self, arrays):
        self.arrays = list(arrays)
        self.pos = 0
        self.new_stats = []

    def take(self, n=1):
        out = self.arrays[self.pos:self.pos + n]
        if len(out) != n:
            raise ValueError("reference: the tape is shorter than the plan")
        self.pos += n
        return out[0] if n == 1 else out

    def done(self):
        if self.pos != len(self.arrays):
            raise ValueError(
                f"reference: {len(self.arrays) - self.pos} arrays left on "
                f"the tape: the plan and the weights disagree")


def conv(x, w, stride, pad, groups=1):
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
        feature_group_count=groups)


def batch_norm(x, tape, train):
    """y = scale * (x - mean) / sqrt(var + eps) + bias over N, H, W; the
    batch's own biased statistics in training, the moving ones otherwise."""
    scale, bias, mean, var = tape.take(4)
    if train:
        m = jnp.mean(x, axis=(0, 1, 2))
        v = jnp.mean(jnp.square(x - m), axis=(0, 1, 2))
        tape.new_stats.append((BN_MOMENTUM * mean + (1 - BN_MOMENTUM) * m,
                               BN_MOMENTUM * var + (1 - BN_MOMENTUM) * v))
    else:
        m, v = mean, var
    return (x - m) / jnp.sqrt(v + BN_EPS) * scale + bias


def conv_bn(x, tape, stride, pad, train, groups=1, relu=True):
    y = batch_norm(conv(x, tape.take(), stride, pad, groups), tape, train)
    return jax.nn.relu(y) if relu else y


def avg_pool(x, k, stride):
    s = lax.reduce_window(x, 0.0, lax.add, (1, k, k, 1),
                          (1, stride, stride, 1), "VALID")
    return s / float(k * k)


def max_pool(x, k, stride, pad):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, k, k, 1), (1, stride, stride, 1),
        ((0, 0), (pad, pad), (pad, pad), (0, 0)))


def dense(x, tape):
    w, b = tape.take(2)
    return x @ w + b


def mean_cross_entropy(logits, labels):
    """Mean over the batch of -log softmax(logits)[label]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels.reshape(-1, 1), axis=1)
    return -jnp.mean(picked)


def conv_out(size, k, stride, pad):
    return (size + 2 * pad - k) // stride + 1


def conv_entry(cin, cout, k, stride, groups, size_out, first=False):
    return dict(kind="conv", cin=cin, cout=cout, k=k, stride=stride,
                groups=groups, h_out=size_out, w_out=size_out, first=first)


def dense_entry(cin, cout):
    """A fully connected layer, which costs what a 1x1 convolution on a
    1x1 image costs."""
    return dict(kind="dense", cin=cin, cout=cout, k=1, stride=1, groups=1,
                h_out=1, w_out=1, first=False)


def _forward(network, cfg, arrays, images, labels, train):
    tape = Tape(arrays)
    logits = network(cfg, tape, images.astype(jnp.float32), train)
    tape.done()
    loss = None if labels is None else mean_cross_entropy(logits, labels)
    return logits, loss, tape.new_stats


def run(network, cfg, arrays, images, labels=None, train=False):
    """(logits, loss or None, new moving statistics) of `network` at full
    float32 precision: on a TPU a float32 product is otherwise rounded to
    bf16 operands. One jitted program, so that the persistent compilation
    cache holds it (op by op, its 400 small compiles are made anew in
    every process)."""
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda a, x, y: _forward(network, cfg, a, x, y, train))
        return fn(list(arrays), images, labels)


def loss_and_grads(network, cfg, arrays, images, labels):
    """Training-mode loss and its gradient to every array of the tape."""
    def f(arrs, x, y):
        return _forward(network, cfg, arrs, x, y, True)[1]

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(f))(list(arrays), images, labels)
