"""Benchmark model zoo (reference benchmark/fluid/models/__init__.py).

Each module exposes get_model(args) -> (avg_cost, inference_program,
optimizer, train_reader, test_reader, batch_acc). args needs .batch_size and
.data_set ("cifar10" | "flowers" | ...). `olmoe` (a decoder language model
with sparse experts) is built from a configuration instead: `olmoe.olmoe(tokens,
cfg)`, `olmoe.olmoe_loss`, `olmoe.optimizer`; so is `xing4` (latent
attention, a residual path of several streams, held and shared experts, a
multi-token-prediction module, as one chip's share of each layer):
`xing4.xing4(tokens, next_tokens, cfg)`, `xing4.xing4_loss`,
`xing4.optimizer`; and `laguna` (window and full attention layers of
different head counts, grouped-query heads, per-head output gates, held
and shared experts, as one chip's share): `laguna.laguna(tokens, cfg)`,
`laguna.laguna_loss`, `laguna.optimizer`; and `smallthinker` (every layer
sparse with ReLU-gated experts routed by the layer's INPUT, before
attention; full attention layers without positions beside sliding-window
layers with rotary; as one chip's share): `smallthinker.smallthinker(tokens,
cfg)`, `smallthinker.smallthinker_loss`, `smallthinker.optimizer`; and
`lfm2` (gated short convolutions in most layers beside grouped-query
attention with per-head QK norm in the rest, a dense SwiGLU first and
sigmoid-routed experts with the model's own bias after, the head the
embedding table itself; as one chip's share of the experts and of the
vocabulary): `lfm2.lfm2(tokens, cfg)`, `lfm2.lfm2_loss`, `lfm2.optimizer`;
and `qwen3_next` (a gated delta rule's matrix-valued state behind a 4-tap
convolution in three of four layers, output-gated grouped-query attention
at heads of 256 in the fourth, softmax top-10 experts beside a gated shared
expert; as one chip's share): `qwen3_next.qwen3_next(tokens, cfg)`,
`qwen3_next.qwen3_next_loss`, `qwen3_next.optimizer`; and `keye_vl`
(grouped-query attention over the keys a learned indexer picks for each
query, the indexer trained beside the model by its own loss, softmax top-8
experts; as one chip's share): `keye_vl.keye_vl(tokens, cfg)`,
`keye_vl.keye_vl_loss`, `keye_vl.optimizer`; and `nemotron_h` (layers of
ONE branch each by a pattern string: Mamba-2 mixers, a selective
state-space scan behind a 4-tap convolution with a bias; grouped-query
attention without positions; sigmoid top-6 un-gated relu^2 experts beside
a shared one; as one chip's share): `nemotron_h.nemotron_h(tokens, cfg)`,
`nemotron_h.nemotron_h_loss`, `nemotron_h.optimizer`.
"""

from . import mnist
from . import resnet
from . import vgg
from . import se_resnext
from . import stacked_dynamic_lstm
from . import machine_translation
from . import olmoe
from . import xing4
from . import laguna
from . import smallthinker
from . import lfm2
from . import qwen3_next
from . import keye_vl
from . import nemotron_h

__all__ = ["mnist", "resnet", "vgg", "se_resnext", "stacked_dynamic_lstm",
           "machine_translation", "olmoe", "xing4", "laguna", "smallthinker",
           "lfm2", "qwen3_next", "keye_vl", "nemotron_h"]


def get_model(name):
    import importlib
    return importlib.import_module(f"paddle_tpu.models.{name}").get_model
