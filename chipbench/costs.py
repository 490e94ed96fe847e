"""Operations and bytes of a convolution, and of a whole model step, as
functions of the shapes alone, and the peaks they are set against.

The compiler's own cost analysis is not used: on this runtime it counts a
`lax.scan` body once whatever K is (PERF.md, PR 21), and a count the
program's compiler makes can move with the program. A convolution here is a
dict of `cin, cout, k, stride, groups, h_out, w_out` (a fully connected
layer is a 1x1 convolution on a 1x1 image); a configuration's builder lists
its convolutions (`chipbench/configs/<config>.py: conv_layers`).
"""

import json
import os

BF16 = 2


def peaks_for(device_kind, path=None):
    """The chip's peaks from the one table; an unknown kind is an error."""
    path = path or os.path.join(os.path.dirname(__file__), "peaks.json")
    with open(path) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in {path}: add its "
            f"published peaks there, with their source")
    return table[device_kind]


def conv_flops(c, batch):
    """Multiply-adds x 2 of one forward pass over `batch` images."""
    return (2 * batch * c["h_out"] * c["w_out"] * c["cout"]
            * (c["cin"] // c["groups"]) * c["k"] * c["k"])


def conv_bytes(c, batch, elem=BF16):
    """Input + filter + output, each moved once, `elem` bytes an element."""
    h_in, w_in = c["h_out"] * c["stride"], c["w_out"] * c["stride"]
    x = batch * h_in * w_in * c["cin"]
    w = c["cout"] * (c["cin"] // c["groups"]) * c["k"] * c["k"]
    y = batch * c["h_out"] * c["w_out"] * c["cout"]
    return (x + w + y) * elem


def conv_passes(c, train):
    """Passes a step makes over one convolution: forward, and in training
    the gradient to the filter and (unless the input is the image) to the
    input. Each moves the same three tensors and does the same
    multiply-adds, with the roles exchanged."""
    if not train:
        return 1
    return 2 if c.get("first") else 3


def step_flops(convs, batch, train):
    """Operations the convolutions and fully connected layers of one step
    need (batch norm, pooling and the optimizer are under 1% and are left
    out, so a utilization built on this is slightly low, never high)."""
    return sum(conv_flops(c, batch) * conv_passes(c, train) for c in convs)


def step_least_seconds(convs, batch, train, peaks):
    """The least time the chip could spend in the convolutions of one
    step: per pass the larger of operations over peak FLOP/s and bytes over
    peak bytes/s. Returns (seconds, seconds if only FLOPs bound, seconds if
    only bytes bound): the larger of the last two says which bound binds."""
    total = by_flops = by_bytes = 0.0
    for c in convs:
        n = conv_passes(c, train)
        tf = conv_flops(c, batch) / peaks["bf16_flops_per_s"]
        tb = conv_bytes(c, batch) / peaks["hbm_bytes_per_s"]
        total += n * max(tf, tb)
        by_flops += n * tf
        by_bytes += n * tb
    return total, by_flops, by_bytes
