"""Transfer engine: compressed wire formats for the host->device link.

Where the fed path is bound by the host->device link, the host-side lever
that raises its ceiling is shrinking bytes-per-sample ON THE WIRE: ship
each feed in a compact wire dtype (uint8 pixels, bf16 activations) and
fuse the cast + affine normalize into the compiled step, where XLA folds
it into the first consumer for free. (Whether the link binds on the
current host has not been measured.)

A WireSpec maps feed names to WireFormats. It rides the pipeline in two
places:

  encode side (host, AsyncDeviceFeeder): each batch is encoded into the
    chunk staging buffer in the wire dtype, so the device_put moves the
    compressed representation;
  decode side (device, Executor/ParallelExecutor): the compiled step is
    wrapped so feed `x` becomes `x.astype(compute_dtype) * scale + shift`
    INSIDE the jit — per scan iteration, so the decompressed tensor never
    materializes at [K, ...] chunk granularity in HBM.

Staged chunk dicts carry the spec under WIRE_KEY (and single-use chunks a
DONATE_KEY marker); Executor.run pops both via pop_markers and extends its
compile-cache key with the spec fingerprint.
"""

import numpy as np

from ..flags import define, get as get_flag

__all__ = ["WireFormat", "WireSpec", "WIRE_KEY", "DONATE_KEY",
           "pop_markers", "auto_wire"]

WIRE_KEY = "__wire__"      # staged-chunk metadata: the chunk's WireSpec
DONATE_KEY = "__donate__"  # staged-chunk metadata: buffers are single-use

define("wire_compress", bool, True,
       "Ship compressed wire formats on the host->device link by default "
       "(uint8 image feeds stay uint8 on the wire; the compiled step "
       "fuses the cast/normalize). FLAGS_wire_compress=0 reverts to "
       "uncompressed float feeds everywhere a pipe or bench path asked "
       "for the default.")


def _np_dtype(name):
    """np.dtype for a wire dtype name; 'bfloat16' resolves via ml_dtypes
    (numpy proper has no bf16)."""
    if str(name) == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


class WireFormat:
    """How ONE feed travels the link.

    wire_dtype:    dtype on the wire (what device_put moves)
    compute_dtype: dtype the compiled step decodes to (None = the program
                   variable's declared dtype, resolved at wrap time)
    scale, shift:  fused on-device affine decode
                       decoded = cast(x, compute_dtype) * scale + shift
                   (and the host encode applies the exact inverse when it
                   must quantize a float source down to an integer wire)
    """

    __slots__ = ("wire_dtype", "compute_dtype", "scale", "shift")

    def __init__(self, wire_dtype, compute_dtype=None, scale=None,
                 shift=None):
        self.wire_dtype = str(_np_dtype(wire_dtype))
        self.compute_dtype = (None if compute_dtype is None
                              else str(_np_dtype(compute_dtype)))
        self.scale = None if scale is None else float(scale)
        self.shift = None if shift is None else float(shift)

    def fingerprint(self):
        return (self.wire_dtype, self.compute_dtype, self.scale, self.shift)

    def __repr__(self):
        parts = [self.wire_dtype]
        if self.compute_dtype:
            parts.append(f"->{self.compute_dtype}")
        if self.scale is not None:
            parts.append(f"*{self.scale:g}")
        if self.shift is not None:
            parts.append(f"+{self.shift:g}")
        return f"WireFormat({' '.join(parts)})"

    # -- host side -------------------------------------------------------
    def encode(self, arr):
        """Host array -> wire array. A source already in the wire dtype
        passes through untouched (the common case: uint8 pixels straight
        from the decoder); a float source quantizing down to an integer
        wire applies the inverse of the on-device affine decode."""
        arr = np.asarray(arr)
        if str(arr.dtype) == self.wire_dtype:
            return arr
        wd = _np_dtype(self.wire_dtype)
        if np.issubdtype(wd, np.integer) and \
                arr.dtype.kind in ("f", "V"):  # V: bf16 views land as void
            x = arr.astype(np.float32)
            if self.shift is not None:
                x = x - self.shift
            if self.scale is not None:
                x = x / self.scale
            info = np.iinfo(wd)
            return np.clip(np.rint(x), info.min, info.max).astype(wd)
        return arr.astype(wd)

    # -- device side (inside jit) ---------------------------------------
    def decode(self, x, var_dtype=None):
        """Wire value -> compute value; traced, so the cast/affine fuse
        into the first consumer."""
        import jax.numpy as jnp

        target = self.compute_dtype or var_dtype or "float32"
        y = x.astype(target) if str(x.dtype) != str(target) else x
        if self.scale is not None:
            y = y * jnp.asarray(self.scale, target)
        if self.shift is not None:
            y = y + jnp.asarray(self.shift, target)
        return y


class WireSpec:
    """{feed_name: WireFormat} for one pipe. Immutable once built; hashable
    via fingerprint() so executors can key compile caches on it."""

    def __init__(self, formats):
        self._formats = {}
        for name, fmt in dict(formats).items():
            if not isinstance(fmt, WireFormat):
                fmt = WireFormat(fmt)
            self._formats[str(name)] = fmt

    # -- common cases ----------------------------------------------------
    @classmethod
    def uint8_images(cls, *names, scale=1.0 / 255.0, shift=None,
                     compute_dtype="float32"):
        """Pixels ride as uint8 (4x fewer link bytes than float32) and the
        compiled step casts + normalizes: x/255 by default."""
        return cls({n: WireFormat("uint8", compute_dtype=compute_dtype,
                                  scale=scale, shift=shift) for n in names})

    @classmethod
    def bfloat16(cls, *names):
        """Float features ride as bf16 (2x fewer link bytes); decode is a
        plain widen to the variable's declared dtype."""
        return cls({n: WireFormat("bfloat16") for n in names})

    # -- mapping surface -------------------------------------------------
    def __contains__(self, name):
        return name in self._formats

    def __getitem__(self, name):
        return self._formats[name]

    def __iter__(self):
        return iter(self._formats)

    def __len__(self):
        return len(self._formats)

    def items(self):
        return self._formats.items()

    def fingerprint(self):
        return tuple(sorted(
            (n, f.fingerprint()) for n, f in self._formats.items()))

    def describe(self):
        """{feed_name: wire-format repr} — the journal-friendly rendering
        of what each covered feed looks like on the wire."""
        return {n: repr(f) for n, f in sorted(self._formats.items())}

    def __repr__(self):
        return f"WireSpec({self._formats!r})"

    # -- pipeline hooks --------------------------------------------------
    def wire_dtype(self, name, sample):
        """Staging-buffer dtype for one feed (the wire dtype when covered,
        the sample's own dtype otherwise)."""
        if name in self._formats:
            return _np_dtype(self._formats[name].wire_dtype)
        return np.asarray(sample).dtype

    def encode_feed(self, feed):
        """Encode every covered entry of a host feed dict (non-covered and
        '__'-metadata entries pass through)."""
        return {n: (self._formats[n].encode(v)
                    if n in self._formats and not n.startswith("__") else v)
                for n, v in feed.items()}

    def wrap_step(self, step, var_dtypes=None):
        """step(mut, const, feeds, rng) -> same signature, with covered
        feeds decoded first. Applied to the PER-STEP function, before any
        multi-step scan wrapper, so the decode runs per iteration on
        [batch, ...] slices."""
        var_dtypes = var_dtypes or {}

        def wired(mut_state, const_state, feeds, rng):
            feeds = dict(feeds)
            for n, fmt in self._formats.items():
                if n in feeds:
                    feeds[n] = fmt.decode(feeds[n], var_dtypes.get(n))
            return step(mut_state, const_state, feeds, rng)

        return wired


def auto_wire(sample):
    """Default WireSpec for a sample dict (`wire="auto"`): every uint8
    feed rides the link as uint8 and the compiled step casts it to the
    program variable's declared dtype — numerically identical to the host
    cast it replaces, at a quarter of the link bytes when the variable is
    float32. Non-uint8 feeds are left alone (quantizing floats would
    change numerics, which is an explicit opt-in via WireSpec). Returns
    None when nothing qualifies or FLAGS_wire_compress=0."""
    if not get_flag("wire_compress") or not isinstance(sample, dict):
        return None
    names = []
    for n, v in sample.items():
        if n.startswith("__"):
            continue
        try:
            a = np.asarray(v)
        except Exception:
            continue
        if a.dtype == np.uint8:
            names.append(n)
    if not names:
        return None
    # pass-through wire + cast-only decode (no affine): the program's
    # declared var dtype resolves at wrap time
    return WireSpec({n: WireFormat("uint8") for n in names})


def pop_markers(feed):
    """Split transfer-engine metadata off a feed dict.

    Returns (feed, wire_spec, donate). The input dict is left untouched —
    a shallow copy is made when markers are present (stage_fn chunks may
    be caller-owned and reused)."""
    if not isinstance(feed, dict) or \
            (WIRE_KEY not in feed and DONATE_KEY not in feed):
        return feed, None, False
    feed = dict(feed)
    wire = feed.pop(WIRE_KEY, None)
    donate = bool(feed.pop(DONATE_KEY, False))
    return feed, wire, donate
