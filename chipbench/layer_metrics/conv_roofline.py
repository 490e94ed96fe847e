"""% of their roofline the convolution fusions reached: the least time the
chip could take for the convolutions' and fully connected layers'
operations and bytes (functions of the shapes, `chipbench/costs.py`, at the
table's peaks), over the time the trace shows in operations XLA files under
`convolution fusion` (on a TPU a matrix product is one too). The steps are
those the traced window ran."""

from chipbench import costs


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("steps_in_window"):
        return None
    spent = sum(s for cat, s in tr["category_seconds"].items()
                if "convolution" in cat)
    if spent <= 0:
        return None
    least, _, _ = costs.step_least_seconds(
        obs["plan"], obs["batch"] // obs["chips"], obs["train"],
        obs["peaks"])
    return 100.0 * least * obs["steps_in_window"] / spent
