"""What the two image configurations share when they are handed to the
system under test: the Fluid program around a model function, the weights
read back out of a scope in program order, and the feeds made from a seed.

A configuration's own builder (`chipbench/configs/<config>.py`) supplies the
model function and the order its reference expects the weights in.
"""

import numpy as np


def image_shape(cfg):
    s, c = cfg["image_size"], cfg["channels"]
    return [s, s, c] if cfg["layout"] == "NHWC" else [c, s, s]


def build_image_program(fluid, cfg, model_fn, seed):
    """uint8 pixels in, cast and scaled on the device, model, softmax
    cross-entropy, Momentum: the program `bench.py` times, plus the
    inference clone taken before the optimizer is appended."""
    opt = cfg["optimizer"]
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        raw = fluid.layers.data(name="data_u8", shape=image_shape(cfg),
                                dtype="uint8")
        img = fluid.layers.scale(fluid.layers.cast(raw, "float32"),
                                 scale=cfg["input_scale"])
        # int32 labels: x64 is off under jax, int64 feeds would re-cast
        label = fluid.layers.data(name="label", shape=[1], dtype="int32")
        predict = model_fn(fluid, cfg, img)
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=predict, label=label))
        test_prog = prog.clone(for_test=True)
        fluid.optimizer.Momentum(learning_rate=opt["learning_rate"],
                                 momentum=opt["momentum"]).minimize(loss)
        prog.random_seed = startup.random_seed = int(seed) % (2 ** 31 - 1) + 1
    return dict(prog=prog, startup=startup, test_prog=test_prog, loss=loss,
                predict=predict, image_feed="data_u8", label_feed="label")


def layers_in_program_order(prog):
    """The weighted layers of a forward program, in the order the program
    runs them: ("conv", [filter]), ("bn", [scale, bias, mean, variance]),
    ("fc", [weight, bias]) with the names of their scope variables."""
    out, ops = [], prog.global_block().ops
    for i, op in enumerate(ops):
        if op.type in ("conv2d", "depthwise_conv2d"):
            out.append(("conv", [op.input("Filter")[0]]))
        elif op.type == "batch_norm":
            out.append(("bn", [op.input(s)[0] for s in
                               ("Scale", "Bias", "Mean", "Variance")]))
        elif op.type == "mul":
            nxt = ops[i + 1] if i + 1 < len(ops) else None
            if nxt is None or nxt.type != "elementwise_add":
                raise ValueError("chipbench: a mul without its bias add")
            out.append(("fc", [op.input("Y")[0], nxt.input("Y")[0]]))
    return out


def read_tape(scope, layers):
    """Float32 host copies of the layers' variables, flat, in order, with
    the variable name of every slot."""
    names = [n for _, group in layers for n in group]
    return [np.asarray(scope.find_var(n), np.float32) for n in names], names


def seeded_images(cfg, seed, count, batch):
    """`count` batches of uint8 pixels and int32 labels from `seed`, NHWC
    as the reference takes them; `to_system` lays one out for the program."""
    rs = np.random.default_rng(int(seed))
    s, c = cfg["image_size"], cfg["channels"]
    xs = rs.integers(0, 256, (count, batch, s, s, c), dtype=np.uint8)
    ys = rs.integers(0, cfg["num_classes"], (count, batch, 1)).astype(np.int32)
    return xs, ys


def to_system(cfg, images_nhwc):
    """NHWC pixels in the layout the configuration's program is fed in."""
    if cfg["layout"] == "NHWC":
        return images_nhwc
    axes = list(range(images_nhwc.ndim - 3)) + [images_nhwc.ndim - 1,
                                                images_nhwc.ndim - 3,
                                                images_nhwc.ndim - 2]
    return np.ascontiguousarray(np.transpose(images_nhwc, axes))


def param_count(prog):
    from paddle_tpu.core.framework import Parameter

    return int(sum(np.prod(v.shape) for v in prog.global_block().vars.values()
                   if isinstance(v, Parameter)))
