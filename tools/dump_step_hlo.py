"""python tools/dump_step_hlo.py <tree> <config> <rows> <out>

The training step of one of the benchmark's configurations as the Executor
lowers it on a TPU place in the checkout <tree>, compiled for a described
v5e WITHOUT the chip (`tests/test_tpu_compile.py: _lm_step`; an image
configuration's step likewise, <rows> its batch), written to <out> with
what differs between two checkouts of the same program dropped: the Mosaic
payloads (`backend_config`), the instructions' `metadata` and the source
tables (they embed file paths and line numbers). `diff` of two trees'
outputs is the check a PR that touches shared lowering code states in
PERF.md ("the accepted programs compile to the parent's HLO": 0 differing
lines in PRs 36, 39, 43, 49, 54). One process at a time: a compile takes a
minute or more and gigabytes of host memory.

    for t in parent change; do python tools/dump_step_hlo.py /path/$t \
        olmoe_1b_7b 2 /tmp/olmoe_$t.txt; done; diff /tmp/olmoe_*.txt | wc -l

Rows: olmoe_1b_7b 2; every other token configuration 1; resnet50 and
se_resnext50 128.
"""

import json
import os
import re
import sys


def _image_step(tree, chip, config, batch):
    import importlib

    import jax
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.core import executor_core

    builder = importlib.import_module("chipbench.configs." + config)
    with open(os.path.join(tree, "chipbench", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    amp.enable("bfloat16")
    try:
        built = builder.build(fluid, cfg, 7)
        gb = built["prog"].global_block()
        wrote = {n for op in gb.ops for n in op.output_arg_names()}
        read = {n for op in gb.ops for n in op.input_arg_names()}
        state = {n: jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype),
                                         sharding=chip)
                 for n, v in gb.vars.items()
                 if v.persistable and n in wrote | read}
        mut = {n: s for n, s in state.items() if n in wrote}
        const = {n: s for n, s in state.items() if n not in wrote}
        hw, ch = cfg["image_size"], cfg["channels"]
        shape = (batch, hw, hw, ch) if cfg["layout"] == "NHWC" \
            else (batch, ch, hw, hw)
        feeds = {"data_u8": jax.ShapeDtypeStruct(shape, np.uint8,
                                                 sharding=chip),
                 "label": jax.ShapeDtypeStruct((batch, 1), np.int32,
                                               sharding=chip)}
        rng = jax.ShapeDtypeStruct((2,), np.uint32, sharding=chip)
        step = executor_core.build_step_fn(
            built["prog"], [built["loss"].name], sorted(mut))
        return jax.jit(step, donate_argnums=(0,)).lower(
            mut, const, feeds, rng).compile()
    finally:
        amp.disable()


class _Patch:
    """`_lm_step` asks a pytest `monkeypatch` for `setattr` alone."""

    def setattr(self, obj, name, value):
        setattr(obj, name, value)


def main(argv):
    tree, config, rows, out = argv
    tree = os.path.abspath(tree)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [tree, os.path.join(tree, "tests")]
    os.chdir(tree)
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    import test_tpu_compile as base
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    if config in ("resnet50", "se_resnext50"):
        compiled = _image_step(tree, chip, config, int(rows))
    else:
        _, compiled = base._lm_step(
            chip, _Patch(), config, int(rows),
            lambda built: [built["routing"][0][1].name])
    lines = []
    for ln in compiled.as_text().splitlines():
        ln = re.sub(r'backend_config="[^"]*"', 'backend_config=""', ln)
        if "tpu_custom_call" in ln:
            ln = re.sub(r"backend_config=\{[^\n]*", "", ln)
        ln = re.sub(r"metadata=\{[^}]*\}", "", ln)
        if ln.startswith(("FileNames", "FunctionNames", "FileLocations",
                          "StackFrames")) or re.match(r"^\d+ ", ln):
            continue
        lines.append(ln)
    with open(out, "w") as f:
        f.write("\n".join(lines))
    print(config, len(lines), "lines; temporaries",
          compiled.memory_analysis().temp_size_in_bytes)


if __name__ == "__main__":
    main(sys.argv[1:5])
