"""% of the device's busy time spent forming the gradient of an embedding
table: every scope key that has the Fluid op `lookup_table_grad` among its
components (`embed/lookup_table_grad`, a second read of the table under
another scope as `mtp/embed/lookup_table_grad`, and a kernel the op's
lowering names beneath either, as `embed/lookup_table_grad/row_tile_sum`).
XLA lowers the op to a sort of the ids, a gather of the rows and a sorted
scatter, which is fast where the table's gradient is assigned to the
chip's fast memory and a read-modify-write against HBM a row where it is
not (PERF.md, PR 38). None where the window holds no such key."""

from chipbench import scopes


def read(obs):
    red = obs.get("scopes")
    if not red or not red["busy_s"]:
        return None
    spent = scopes.seconds(red, "lookup_table_grad")
    return 100.0 * spent / red["busy_s"] if spent else None
