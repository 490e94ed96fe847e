"""se_resnext50: how the configuration is handed to the system under test,
and how the system's weights are laid out for the plain reference."""

from chipbench import programs
from chipbench.reference import se_resnext50 as reference  # noqa: F401


def model(fluid, cfg, img):
    from paddle_tpu.models.se_resnext import se_resnext

    return se_resnext(img, cfg["num_classes"], depth=cfg["depth"])


def build(fluid, cfg, seed, for_compare=False):
    """for_compare: the one dropout op of the TRAINING program gets
    probability 0 (it keeps everything), because its mask comes from the
    system's own random stream and no reference can reproduce it; the
    reference's training pass is given dropout 0 as well
    (`compare_train_cfg`). Everything else, batch statistics included,
    stays in training mode, and the inference program keeps its 0.8."""
    built = programs.build_image_program(fluid, cfg, model, seed)
    if for_compare:
        for op in built["prog"].global_block().ops:
            if op.type in ("dropout", "dropout_grad"):
                op.set_attr("dropout_prob", 0.0)
    return built


def compare_train_cfg(cfg):
    return dict(cfg, dropout=0.0)


def reference_order(layers, cfg):
    """The system builds conv a, b, c, the two SE layers, then the
    shortcut: the order of the reference's tape."""
    return list(layers)
