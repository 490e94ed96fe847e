"""Device places.

Reference parity: paddle/fluid/platform/place.h:25-49 (CPUPlace / CUDAPlace /
CUDAPinnedPlace). The TPU build's first-class accelerator place is TPUPlace;
CUDAPlace is accepted as an alias for the accelerator place so reference-style
scripts run unmodified (they do `fluid.CUDAPlace(0)`).
"""

import jax
# `with mesh:` (how ParallelExecutor traces) is visible through no public
# API in jax 0.9.0: jax.sharding.get_abstract_mesh() stays empty inside it
from jax._src.mesh import thread_resources


class Place:
    def __eq__(self, other):
        return type(self) is type(other) and getattr(self, "device_id", 0) == getattr(
            other, "device_id", 0
        )

    def __hash__(self):
        return hash((type(self).__name__, getattr(self, "device_id", 0)))

    def __repr__(self):
        return type(self).__name__ + "()"


class CPUPlace(Place):
    """Host CPU."""

    platform = "cpu"


class TPUPlace(Place):
    """A single TPU chip (by local device index)."""

    platform = "tpu"

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


# API-parity alias: reference scripts say CUDAPlace(0); here it means
# "the accelerator" — the same device TPUPlace(0) names.
class CUDAPlace(TPUPlace):
    def __repr__(self):
        return f"CUDAPlace({self.device_id})"


class CUDAPinnedPlace(CPUPlace):
    """Pinned host memory place (host staging buffers). On TPU, host->device

    transfer staging is managed by PjRt; this exists for API parity."""


def is_compiled_with_tpu():
    try:
        return any(d.platform != "cpu" for d in jax.devices())
    except RuntimeError:
        return False


# reference API parity (`core.is_compiled_with_cuda`, pybind.cc)
def is_compiled_with_cuda():
    return is_compiled_with_tpu()


def accelerator_count():
    """Number of local accelerator devices (get_cuda_device_count parity)."""
    return len([d for d in jax.devices() if d.platform != "cpu"]) or 0


def place_to_str(place):
    """Serialize a Place for op attrs / JSON IR ('cpu', 'tpu:0', ...)."""
    if isinstance(place, TPUPlace):
        return f"tpu:{place.device_id}"
    return "cpu"


def place_from_str(s):
    if s == "cpu" or not s:
        return CPUPlace()
    kind, _, idx = s.partition(":")
    if kind not in ("tpu", "cuda", "gpu"):
        raise ValueError(f"unknown place string {s!r}")
    return TPUPlace(int(idx or 0))


def cpu_pinned():
    """True when the platform list was explicitly pinned to the CPU
    (JAX_PLATFORMS=cpu or jax.config.update("jax_platforms", "cpu") — what
    tests/conftest.py and the CPU verify recipe do). A pin is a choice the
    caller made; a missing accelerator without one is an error."""
    platforms = jax.config.jax_platforms or ""
    names = [p.strip() for p in platforms.split(",") if p.strip()]
    return bool(names) and all(n == "cpu" for n in names)


def accelerator_devices(devs=None):
    """The devices an accelerator place indexes: the non-CPU ones of
    `devs` (default: this process's local devices; ParallelExecutor passes
    the global list), or — only under an explicit CPU pin — XLA's host
    devices. Raises when there is no accelerator and no pin: JAX falls
    back to the CPU with a warning when the TPU client fails to
    initialise, and a place that followed it there would make a CPU run
    look like a chip run."""
    devs = jax.local_devices() if devs is None else list(devs)
    accel = [d for d in devs if d.platform != "cpu"]
    if accel:
        return accel
    if cpu_pinned():
        return devs
    raise RuntimeError(
        f"no accelerator: no non-CPU device among {devs} and the platform "
        "list is not pinned to the CPU. Run on a machine with a TPU, or "
        "pin the CPU explicitly with JAX_PLATFORMS=cpu for a rehearsal.")


def jax_device_for(place):
    """Map a Place to a concrete jax.Device (place.h:25-49 semantics).

    CPUPlace resolves via the host platform directly
    (``jax.local_devices(backend="cpu")``), NOT by scanning the default
    backend's device list: when the default backend is an accelerator,
    ``jax.devices()`` holds no cpu device.

    Places address LOCAL devices (reference place.h: CUDAPlace(i) is the
    i-th local GPU): under jax.distributed the global device list starts
    with process 0's devices, so indexing jax.devices() would hand every
    other process a non-addressable device it cannot execute on.

    TPUPlace(i) is the i-th device of accelerator_devices(); an index
    beyond the device count raises instead of wrapping onto a chip that
    exists."""
    if isinstance(place, CPUPlace):
        return jax.local_devices(backend="cpu")[0]
    accel = accelerator_devices()
    i = int(getattr(place, "device_id", 0))
    if not 0 <= i < len(accel):
        raise ValueError(
            f"{place!r}: this process has {len(accel)} "
            f"{accel[0].platform} device(s); device_id must be in "
            f"[0, {len(accel)})")
    return accel[i]


def ambient_mesh():
    """The physical Mesh of the enclosing `with mesh:` (ParallelExecutor
    traces its step inside one), or None."""
    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def trace_device():
    """The device the computation being traced right now is compiled for:
    the ambient mesh's first device, else the jax.default_device an
    Executor's place set (Executor._device_scope), else the default
    backend's first local device."""
    mesh = ambient_mesh()
    if mesh is not None:
        return mesh.devices.flat[0]
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.local_devices()[0]
    if isinstance(dev, str):  # jax.default_device("cpu")
        return jax.local_devices(backend=dev)[0]
    return dev


def pallas_interpret():
    """interpret= for a pallas_call traced now, decided from where the
    step will run and not from jax.devices()[0]: Mosaic compiles the
    kernel on a TPU place, the Pallas interpreter runs it anywhere else."""
    return trace_device().platform != "tpu"
