"""% of the device's busy time in operations that no scope of the program
names (`scopes.unscoped_share`: events whose `tf_op` holds no component
of the program's, less the expert layer's products XLA names itself).
Every op of a step lowers under a scope, its own type at the least
(`paddle_tpu/core/executor_core.py: _device_scope`), so what is left is
XLA's own: copies, `copy-done`, sorts, what it hoists out of the scan."""

from chipbench import scopes


def read(obs):
    red = obs.get("scopes")
    return scopes.unscoped_share(red) if red else None
