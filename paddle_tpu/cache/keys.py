"""Stable content digests for the persistent compile cache (L2).

The in-memory compile-cache key pins a program by (id(program),
program._mutation) — perfect within a process, meaningless across
processes. The L2 key replaces that pair with a sha256 of the program's
canonical JSON serialization (Program.desc_str: sort_keys, ops in program
order) and appends everything else that changes the compiled executable:

  * the in-memory key's content tail (feed shape/dtype specs, fetch and
    state name tuples, amp/wire/donate/iters/health and — on the
    ParallelExecutor — zero1/overlap/autoshard digests), which is already
    process-stable by construction (sorted tuples of primitives; no id()s,
    no hash()es)
  * the lowering: a digest of this package's own sources (the kernels, the
    trace-time peepholes and the executors' wrappers decide what a program
    compiles to, and none of them is in the program's serialization)
  * the runtime environment: jax + jaxlib versions, the backend platform
    and the PJRT platform_version, i.e. the libtpu build (an executable
    serialized by one XLA build must never be fed to another — the store
    ALSO stamps these in the entry header and re-checks at load)
  * the device geometry the caller passes as `extra` (device ids, mesh
    axis names/sizes): a serialized executable is bound to its device
    assignment, so a resized mesh takes a clean miss instead of a
    deserialize-time failure.

Never hash() anything here: PYTHONHASHSEED makes it process-local. The
cross-process stability contract is asserted by a subprocess test in
tests/test_compile_cache.py.
"""

import hashlib

__all__ = ["program_digest", "stable_digest", "environment", "is_digest",
           "lowering_version"]

_HEX = set("0123456789abcdef")


def is_digest(value):
    """True for a well-formed sha256 hex key. The compile service
    validates digests at its RPC boundary with this — a digest is also a
    filename under FLAGS_compile_cache_dir, so an unvalidated one from a
    peer would be a path-traversal vector."""
    return (isinstance(value, str) and len(value) == 64
            and set(value) <= _HEX)

# program content digests, keyed (id(program), mutation) — sha256 of a big
# JSON string is the expensive part, and it is only ever needed on the
# compile-cache miss path, so a small FIFO memo keeps repeat misses (new
# feed shapes against one program) from re-serializing the ProgramDesc
_digest_memo = {}
_DIGEST_MEMO_CAP = 128


def program_digest(program):
    """sha256 hex of the program's canonical serialization."""
    key = (id(program), program._mutation)
    hit = _digest_memo.get(key)
    if hit is not None:
        return hit
    d = hashlib.sha256(program.desc_str().encode("utf-8")).hexdigest()
    while len(_digest_memo) >= _DIGEST_MEMO_CAP:
        _digest_memo.pop(next(iter(_digest_memo)))
    _digest_memo[key] = d
    return d


def environment():
    """(jax, jaxlib, backend platform, PJRT platform_version) stamped into
    every entry and folded into every digest — a version bump is an
    automatic cold start. platform_version carries the runtime build (on a
    TPU the libtpu build date and change number): jax and jaxlib can agree
    while libtpu differs, and an AOT blob from another libtpu does not
    load."""
    import jax
    import jaxlib
    from jax.extend import backend as jax_backend

    client = jax_backend.get_backend()
    return (jax.__version__, jaxlib.__version__, client.platform,
            client.platform_version)


_lowering_version = None


def lowering_version():
    """sha256 hex over this package's Python sources. The program digest
    is the program's CONTENT, not what it lowers to: the same program
    under other kernels, peepholes or executor wrappers is another
    executable, so an upgrade of the package is a cold start, like an
    upgrade of jax — with no salt to remember to bump. Read once a
    process (2 MB, a few ms), on the first compile-cache miss."""
    global _lowering_version
    if _lowering_version is None:
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        h = hashlib.sha256()
        for folder, dirs, files in os.walk(root):
            dirs.sort()
            for name in sorted(f for f in files if f.endswith(".py")):
                path = os.path.join(folder, name)
                h.update(os.path.relpath(path, root).encode("utf-8") + b"\0")
                with open(path, "rb") as f:
                    h.update(f.read() + b"\0")
        _lowering_version = h.hexdigest()
    return _lowering_version


def stable_digest(program, key_tail, extra=()):
    """Hex digest naming one L2 entry.

    key_tail: the in-memory cache key MINUS its (id, mutation) head —
    tuples of primitives whose repr is process-stable. extra: caller
    context (executor kind, device ids, mesh geometry).
    """
    h = hashlib.sha256()
    h.update(b"paddle_tpu-aot-v1\0")
    h.update(repr(environment()).encode("utf-8"))
    h.update(b"\0")
    h.update(lowering_version().encode("utf-8"))
    h.update(b"\0")
    h.update(program_digest(program).encode("utf-8"))
    h.update(b"\0")
    h.update(repr(tuple(key_tail)).encode("utf-8"))
    h.update(b"\0")
    h.update(repr(tuple(extra)).encode("utf-8"))
    return h.hexdigest()
