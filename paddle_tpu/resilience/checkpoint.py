"""Async atomic checkpointing: snapshot in the step gap, write off-thread.

Layout (shares the io.py `checkpoint_<serial>/_SUCCESS` naming so
io._get_latest_checkpoint_serial sees both formats):

    <dir>/checkpoint_<serial>/
        state.npz       every checkpoint var (params + optimizer state),
                        host numpy, non-pickled
        manifest.json   step counter, var index, datapipe source position,
                        monitor step counter, caller extras
        _SUCCESS        written INSIDE the temp dir; the dir only appears
                        under its final name via os.replace, so a reader
                        can never observe a half-written checkpoint

Write protocol: serialize + fsync every file into `checkpoint_<N>.tmp`,
fsync the temp dir, os.replace to the final name, fsync the parent —
rename-atomicity end to end (a crash at ANY point leaves either the
previous checkpoint set intact or a `.tmp` orphan that the LRU sweep
removes). The device is never blocked: save() snapshots persistables to
host (the only step-gap cost) and a single background writer thread does
the serialization, so checkpoint cadence costs the training loop one
device_get, not one fsync.
"""

import json
import os
import queue
import threading
import time

import numpy as np

from .. import flags
from .. import monitor

__all__ = ["CheckpointManager", "inspect_dir", "check_mesh_compat"]

MANIFEST_FILENAME = "manifest.json"
STATE_FILENAME = "state.npz"
FORMAT = "resilience-v1"


def _fsync_dir(path):
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:  # platforms without dir fds
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_fsync(path, data, mode="w"):
    with open(path, mode) as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())


def check_mesh_compat(ckpt_mesh, expect_mesh):
    """Refuse a restore whose mesh geometry conflicts with the target.

    The dp axis is layout-independent by contract (zero1/autoshard
    snapshots are canonical full layout, so a dp=8 checkpoint restores
    onto dp=4 bitwise) and may differ freely. Every OTHER axis (mp/pp/sp)
    changes what the saved tensors MEAN — a silent mismatch is silent
    corruption — so any difference raises ValueError. Missing axes count
    as size 1; either side None skips the check (pre-mesh checkpoints
    stay restorable)."""
    if not ckpt_mesh or not expect_mesh:
        return
    from ..parallel.mesh import DP_AXIS

    axes = set(ckpt_mesh) | set(expect_mesh)
    for ax in sorted(axes):
        if ax == DP_AXIS:
            continue
        have = int(ckpt_mesh.get(ax, 1))
        want = int(expect_mesh.get(ax, 1))
        if have != want:
            raise ValueError(
                f"checkpoint mesh geometry conflict on axis {ax!r}: "
                f"checkpoint was saved with {ax}={have}, the target mesh "
                f"has {ax}={want}. Only the dp axis may change across a "
                f"restore (layout-independent contract); re-shard the "
                f"model or restore onto a mesh with matching {ax}.")


def _host_value(v):
    """Best-effort var value -> host numpy array (None = not storable)."""
    from ..core.lod_tensor import LoDTensor
    from ..core.registry import SeqTensor

    if isinstance(v, LoDTensor):
        if v.lod():
            return None  # ragged persistables don't round-trip through npz
        v = v.numpy()
    if isinstance(v, SeqTensor):
        return None
    try:
        import jax

        if isinstance(v, jax.Array) and not getattr(
                v, "is_fully_addressable", True):
            from jax.experimental import multihost_utils

            v = multihost_utils.process_allgather(v, tiled=True)
        arr = np.asarray(jax.device_get(v) if isinstance(v, jax.Array)
                         else v)
    except Exception:
        try:
            arr = np.asarray(v)
        except Exception:
            return None
    if arr.dtype == object:
        return None
    return arr


class CheckpointManager:
    """Async atomic checkpoints of a scope's checkpoint vars.

    checkpoint_dir:       root directory (created on first save)
    max_num_checkpoints:  LRU retention (io._lru_delete semantics)
    async_write:          False = save() blocks until the rename lands
                          (tests, final checkpoint before exit)
    predicate:            var filter; default io._is_checkpoint_var
                          (persistables minus feed/fetch/reader/grads)
    """

    def __init__(self, checkpoint_dir, max_num_checkpoints=3,
                 async_write=True, predicate=None):
        self.checkpoint_dir = str(checkpoint_dir)
        self.max_num_checkpoints = int(max_num_checkpoints)
        self.async_write = bool(async_write)
        self._predicate = predicate
        # mesh geometry ({axis: size}) stamped into every manifest; None =
        # read the ambient parallel.mesh.current_mesh() at save time
        self.mesh_axes = None
        self._serial = None        # last assigned serial
        self._pending = queue.Queue(maxsize=2)  # bounds host snapshots held
        self._writer = None
        self._write_error = [None]
        self._lock = threading.Lock()
        self._closed = False

    # ------------------------------------------------------------- internals
    def _pred(self):
        if self._predicate is not None:
            return self._predicate
        from .. import io as io_mod

        return io_mod._is_checkpoint_var

    def _ensure_writer(self):
        if self._writer is None or not self._writer.is_alive():
            self._writer = threading.Thread(
                target=self._writer_loop, daemon=True,
                name="resilience-ckpt-writer")
            self._writer.start()

    def _writer_loop(self):
        while True:
            job = self._pending.get()
            if job is None:
                return
            try:
                self._write_one(*job)
            except Exception as e:
                # surfaced to the caller on the next save()/wait()
                self._write_error[0] = e
            finally:
                self._pending.task_done()

    def _serial_dir(self, serial):
        from .. import io as io_mod

        return io_mod._get_serial_dir(serial, self.checkpoint_dir)

    def _next_serial(self):
        from .. import io as io_mod

        latest = io_mod._get_latest_checkpoint_serial(self.checkpoint_dir)
        with self._lock:
            nxt = max(latest, self._serial if self._serial is not None
                      else -1) + 1
            self._serial = nxt
        return nxt

    def _write_one(self, serial, snap, manifest):
        from .. import io as io_mod

        t0 = time.perf_counter()
        final_dir = self._serial_dir(serial)
        tmp_dir = final_dir + ".tmp"
        if os.path.isdir(tmp_dir):
            import shutil

            shutil.rmtree(tmp_dir, ignore_errors=True)
        os.makedirs(tmp_dir)
        with open(os.path.join(tmp_dir, STATE_FILENAME), "wb") as f:
            np.savez(f, **snap)
            f.flush()
            os.fsync(f.fileno())
        _write_fsync(os.path.join(tmp_dir, MANIFEST_FILENAME),
                     json.dumps(manifest, indent=1, sort_keys=True))
        _write_fsync(os.path.join(tmp_dir,
                                  io_mod.SUCCESS_MARK_FILENAME),
                     time.ctime())
        _fsync_dir(tmp_dir)
        os.replace(tmp_dir, final_dir)  # the atomic commit point
        _fsync_dir(self.checkpoint_dir)
        io_mod._lru_delete(self.checkpoint_dir, self.max_num_checkpoints)
        ms = (time.perf_counter() - t0) * 1000.0
        reg = monitor.registry()
        reg.counter("checkpoints_saved_total",
                    help="atomic checkpoints committed").inc()
        reg.gauge("checkpoint_write_ms",
                  help="serialize+fsync+rename wall time of the last "
                       "checkpoint (background thread)").set(ms)
        reg.histogram("checkpoint_write_ms_hist",
                      help="checkpoint write time distribution").observe(ms)

    # ------------------------------------------------------------------- API
    def snapshot_vars(self, scope=None, program=None):
        """{name: host ndarray} of the program's checkpoint vars currently
        in scope — the step-gap cost of a save."""
        from .. import io as io_mod
        from ..core.framework import default_main_program
        from ..core.scope import global_scope

        scope = scope if scope is not None else global_scope()
        program = program if program is not None else default_main_program()
        snap = {}
        # less the low-precision copies kept beside their masters: the
        # step that next reads one casts it from the restored master
        for var in io_mod.stored_vars(program, self._pred()):
            v = scope.find_var(var.name)
            if v is None:
                continue
            arr = _host_value(v)
            if arr is not None:
                snap[var.name] = arr
        return snap

    def save(self, step, scope=None, program=None, pipe=None, extra=None,
             block=False):
        """Snapshot now, commit in the background; returns the serial.

        step:  the caller's global step counter (manifest `step`)
        pipe:  a datapipe.DataPipe whose source position rides the
               manifest (checkpoint_state) so restore resumes mid-epoch
        extra: caller dict merged into the manifest (JSON-serializable)
        block: wait for the rename before returning (overrides
               async_write=True for this call)
        """
        if self._closed:
            raise RuntimeError("CheckpointManager is closed")
        self._raise_pending_error()
        snap = self.snapshot_vars(scope=scope, program=program)
        # ZeRO-1 (parallel.zero1): optimizer accumulators live on-device in
        # [dp, shard] padded layout; checkpoints always store the canonical
        # full layout so a checkpoint restores bitwise onto ANY dp size —
        # including FLAGS_zero1=0. The shard layout rides the manifest for
        # `checkpoint inspect`.
        from ..parallel import zero1 as _zero1

        snap, zinfo = _zero1.canonicalize_snapshot(snap)
        serial = self._next_serial()
        manifest = {
            "format": FORMAT,
            "serial": serial,
            "step": int(step),
            "ts": time.time(),
            "vars": {n: {"dtype": str(a.dtype), "shape": list(a.shape)}
                     for n, a in snap.items()},
        }
        if zinfo:
            manifest["zero1"] = zinfo
        # Autoshard (parallel.autoshard): mp-sharded params are gathered to
        # host by _host_value, so the snapshot is already the canonical full
        # layout; the active plan's digest + per-param specs ride the
        # manifest (mirroring the zero1 contract) so `checkpoint inspect`
        # shows the layout and restores stay layout-independent.
        from ..parallel import autoshard as _autoshard

        ainfo = _autoshard.manifest_section(snap)
        if ainfo:
            manifest["autoshard"] = ainfo
        # Pipeline parallelism (parallel.pipeline): stage count, pp axis,
        # microbatches, schedule. Purely descriptive — the snapshot holds
        # every stage's params in full layout — but `checkpoint inspect`
        # renders it, and the pp axis also rides the mesh section below,
        # where check_mesh_compat refuses a pp-mismatched restore.
        from ..parallel import pipeline as _pipeline

        pinfo = _pipeline.manifest_section()
        if pinfo:
            manifest["pipeline"] = pinfo
        # Mesh geometry: which {axis: size} shape produced this state.
        # Restores compare it against the target mesh and refuse a non-dp
        # conflict (check_mesh_compat) instead of silently corrupting.
        mesh_axes = self.mesh_axes
        if mesh_axes is None:
            from ..parallel import mesh as _mesh

            mesh_axes = _mesh.mesh_geometry(_mesh.current_mesh())
        if mesh_axes:
            manifest["mesh"] = {str(a): int(s)
                                for a, s in mesh_axes.items()}
        if pipe is not None and hasattr(pipe, "checkpoint_state"):
            manifest["datapipe"] = pipe.checkpoint_state()
        if monitor.enabled():
            manifest["monitor"] = {"steps": monitor.steps_done()}
        if extra:
            manifest["extra"] = dict(extra)
        os.makedirs(self.checkpoint_dir, exist_ok=True)
        if self.async_write and not block:
            self._ensure_writer()
            self._pending.put((serial, snap, manifest))
        else:
            self._write_one(serial, snap, manifest)
        return serial

    def _raise_pending_error(self):
        e, self._write_error[0] = self._write_error[0], None
        if e is not None:
            raise e

    def wait(self):
        """Block until every queued write has committed (or raise the
        background writer's failure)."""
        self._pending.join()
        self._raise_pending_error()

    def close(self):
        if self._closed:
            return
        self._closed = True
        if self._writer is not None and self._writer.is_alive():
            self._pending.put(None)
            self._writer.join(timeout=30.0)
        self._raise_pending_error()

    # --------------------------------------------------------------- restore
    def latest_serial(self):
        from .. import io as io_mod

        return io_mod._get_latest_checkpoint_serial(self.checkpoint_dir)

    def restore(self, scope=None, program=None, place=None, serial=None,
                expect_mesh=None):
        """Load the latest (or given) checkpoint's vars into `scope` as
        device arrays; returns the manifest dict, or None when no
        successful checkpoint exists. Restoring a serial written by
        io.save_checkpoint (no manifest) raises — use io.load_checkpoint
        for the op-based format. expect_mesh ({axis: size}) refuses the
        restore on a non-dp geometry conflict (check_mesh_compat) BEFORE
        any var is touched."""
        from ..core.scope import global_scope

        serial = self.latest_serial() if serial is None else int(serial)
        if serial < 0:
            return None
        cur_dir = self._serial_dir(serial)
        mpath = os.path.join(cur_dir, MANIFEST_FILENAME)
        if not os.path.isfile(mpath):
            raise ValueError(
                f"{cur_dir} is not a resilience checkpoint (no manifest); "
                f"io.load_checkpoint reads the op-based format")
        with open(mpath) as f:
            manifest = json.load(f)
        if expect_mesh is not None:
            check_mesh_compat(manifest.get("mesh"), expect_mesh)
        scope = scope if scope is not None else global_scope()
        names = None
        if program is not None:
            pred = self._pred()
            names = {v.name for v in program.list_vars() if pred(v)}
        import jax

        dev = None
        if place is not None:
            from ..core.places import jax_device_for

            dev = jax_device_for(place)
        with np.load(os.path.join(cur_dir, STATE_FILENAME)) as data:
            for n in data.files:
                if names is not None and n not in names:
                    continue
                scope.var(n)
                scope.set_var(n, jax.device_put(data[n], dev))
        monitor.registry().counter(
            "checkpoint_restores_total",
            help="checkpoints restored into a scope").inc()
        if monitor.enabled() and "monitor" in manifest:
            monitor.restore_steps(manifest["monitor"].get("steps", 0))
        return manifest


def inspect_dir(checkpoint_dir, serial=None):
    """Checkpoint-directory summary for the CLI: serials found, which are
    committed (_SUCCESS), and the chosen serial's manifest / var files.
    Handles both the resilience format and io.save_checkpoint's."""
    from .. import io as io_mod

    out = {"checkpoint_dir": str(checkpoint_dir), "serials": [],
           "latest": -1}
    if not os.path.isdir(checkpoint_dir):
        out["error"] = "no such directory"
        return out
    for name in sorted(os.listdir(checkpoint_dir)):
        path = os.path.join(checkpoint_dir, name)
        if not os.path.isdir(path):
            continue
        entry = {"dir": name}
        if name.endswith(".tmp"):
            entry["status"] = "orphaned-tmp"
            out["serials"].append(entry)
            continue
        try:
            entry["serial"] = int(
                name.split(io_mod.CHECKPOINT_SEPARATOR)[-1])
        except ValueError:
            continue
        committed = os.path.isfile(
            os.path.join(path, io_mod.SUCCESS_MARK_FILENAME))
        entry["status"] = "committed" if committed else "incomplete"
        entry["bytes"] = sum(
            os.path.getsize(os.path.join(path, f))
            for f in os.listdir(path)
            if os.path.isfile(os.path.join(path, f)))
        out["serials"].append(entry)
    out["latest"] = io_mod._get_latest_checkpoint_serial(checkpoint_dir)
    pick = out["latest"] if serial is None else int(serial)
    if pick >= 0:
        cur = io_mod._get_serial_dir(pick, checkpoint_dir)
        mpath = os.path.join(cur, MANIFEST_FILENAME)
        if os.path.isfile(mpath):
            with open(mpath) as f:
                out["manifest"] = json.load(f)
        elif os.path.isdir(cur):
            out["files"] = sorted(
                f for f in os.listdir(cur)
                if f != io_mod.SUCCESS_MARK_FILENAME)
            out["format"] = "io-save-ops"
    return out
