"""MFU accounting: chip peak table + achieved-FLOPs arithmetic.

Raw img/s without a statement of chip peak, per-step model FLOPs and MFU
cannot tell a throughput plateau from chip saturation. This module owns
those inputs: a per-device-kind dense peak table (overridable via
FLAGS_monitor_chip_peak_tflops for chips the table doesn't know), and the
mfu() formula

    mfu = model_flops_per_step * steps_per_sec / chip_peak_flops

where model_flops_per_step comes from the HLO cost analysis captured at
lowering (monitor.compile_probe) — i.e. the FLOPs XLA says the compiled
step executes, not a hand-waved model estimate.
"""

from .. import flags

__all__ = ["CHIP_PEAK_TFLOPS", "chip_peak_flops", "mfu"]

flags.define(
    "monitor_chip_peak_tflops", float, 0.0,
    "Dense peak TFLOP/s of one chip for MFU accounting, overriding the "
    "built-in per-device-kind table (0 = use the table; unknown kinds "
    "report mfu=null rather than a made-up denominator).")

# Dense bf16 matmul peak per CHIP (all cores), TFLOP/s — published numbers.
# Keys are matched case-insensitively as substrings of
# jax.Device.device_kind, longest match wins ("TPU v5 lite" before "TPU v5").
CHIP_PEAK_TFLOPS = {
    "TPU v2": 45.0,
    "TPU v3": 123.0,
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,   # v5e device_kind spelling
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,   # v6e (Trillium)
    "TPU v6e": 918.0,
}


def chip_peak_flops(device=None):
    """Peak FLOP/s of one chip, or None when unknown.

    Resolution order: FLAGS_monitor_chip_peak_tflops override, then the
    device_kind table. CPU / unknown accelerators return None — mfu() then
    reports null instead of a fictitious utilization."""
    override = flags.get("monitor_chip_peak_tflops")
    if override:
        return float(override) * 1e12
    if device is None:
        try:
            import jax

            device = jax.devices()[0]
        except Exception:
            return None
    kind = str(getattr(device, "device_kind", "") or "")
    best = None
    for name, tflops in CHIP_PEAK_TFLOPS.items():
        if name.lower() in kind.lower():
            if best is None or len(name) > len(best[0]):
                best = (name, tflops)
    return best[1] * 1e12 if best else None


def mfu(model_flops_per_step, steps_per_sec, peak_flops=None, device=None):
    """Model FLOPs utilization in [0, 1]; None when any input is unknown
    (no peak for this chip, no HLO cost captured)."""
    if peak_flops is None:
        peak_flops = chip_peak_flops(device)
    if not peak_flops or not model_flops_per_step or not steps_per_sec:
        return None
    return float(model_flops_per_step) * float(steps_per_sec) / \
        float(peak_flops)
