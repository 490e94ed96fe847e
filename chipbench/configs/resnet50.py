"""resnet50: how the configuration is handed to the system under test, and
how the system's weights are laid out for the plain reference."""

from chipbench import programs
from chipbench.reference import resnet50 as reference  # noqa: F401


def model(fluid, cfg, img):
    from paddle_tpu.models.resnet import resnet_imagenet

    return resnet_imagenet(img, cfg["num_classes"], depth=cfg["depth"],
                           layout=cfg["layout"])


def build(fluid, cfg, seed, for_compare=False):
    return programs.build_image_program(fluid, cfg, model, seed)


def reference_order(layers, cfg):
    """The system builds a projection block's shortcut BEFORE its three
    convolutions; the reference's tape has it after them."""
    out, i = list(layers[:2]), 2          # conv1, bn1
    cin = cfg["stem_width"]
    for count, width in zip(cfg["blocks"], cfg["widths"]):
        for _ in range(count):
            cout = width * cfg["expansion"]
            n = 8 if cin != cout else 6   # (conv, bn) pairs
            group = layers[i:i + n]
            out += group[2:] + group[:2] if n == 8 else group
            i += n
            cin = cout
    return out + list(layers[i:])
