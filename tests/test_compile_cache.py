"""paddle_tpu.cache: the pluggable two-level compile cache.

L1 true-LRU semantics (a hot entry survives the cap), process-stable L2
digests (proven across subprocesses with different PYTHONHASHSEEDs), the
warm-start zero-miss contract, corrupt/stale entries that fall back to a
fresh compile — counted, never raised — store maintenance (prune/clear),
the `paddle_tpu cache` CLI, and the monitor-summary rendering.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, monitor
from paddle_tpu.cache import (CompileCache, L2Store, program_digest,
                              stable_digest)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

@pytest.fixture(autouse=True)
def _fresh_monitor():
    monitor.reset()
    yield
    monitor.reset()


def _flip_tail(path, n=8):
    """Corrupt an entry's PAYLOAD in place. The tail is always payload:
    the header JSON sits at the front of the file, and a flipped byte
    inside one of its hex strings still parses — the payload checksum is
    the integrity boundary, so that corruption is undetectable by design."""
    with open(path, "r+b") as f:
        f.seek(-n, 2)
        tail = f.read(n)
        f.seek(-n, 2)
        f.write(bytes(b ^ 0xFF for b in tail))


# ---------------------------------------------------------------------------
# L1: true LRU under FLAGS_compile_cache_cap
# ---------------------------------------------------------------------------

def test_l1_hot_entry_survives_cap_eviction():
    # the regression the refactor fixes: the old per-executor dicts popped
    # INSERTION order at the cap, evicting the hottest entry first
    cc = CompileCache("executor")
    with flags.flag_guard(compile_cache_cap=2):
        cc.put("a", 1)
        cc.put("b", 2)
        assert cc.get("a") == 1  # refresh a's recency
        cc.put("c", 3)  # must evict b (least recently USED), not a
    assert "a" in cc and "c" in cc and "b" not in cc
    assert cc.evictions == 1
    assert cc.info()["evictions"] == 1


def test_l1_reput_of_resident_key_at_cap_evicts_nothing():
    cc = CompileCache()
    with flags.flag_guard(compile_cache_cap=2):
        cc.put("a", 1)
        cc.put("b", 2)
        cc.put("a", 10)  # refresh, not insert: no room needed
    assert cc.evictions == 0
    assert cc["a"] == 10 and "b" in cc


def test_l1_counters_and_mapping_surface():
    cc = CompileCache()
    assert cc.get("missing") is None
    cc.put("k", "v")
    assert cc.get("k") == "v"
    assert len(cc) == 1 and list(cc) == ["k"] and cc["k"] == "v"
    assert "k" in cc and list(cc.items()) == [("k", "v")]
    info = cc.info()
    assert info["entries"] == 1
    assert info["hits"] == 1 and info["misses"] == 1
    cc.clear()
    assert len(cc) == 0


# ---------------------------------------------------------------------------
# L2 digests: content-addressed, process-stable
# ---------------------------------------------------------------------------

def _mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(input=x, size=4))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_program_digest_is_content_addressed():
    m1, _, _ = _mlp()
    m2, _, _ = _mlp()
    assert m1 is not m2
    assert program_digest(m1) == program_digest(m2)
    # a mutation bump with UNCHANGED content keeps the digest (the memo is
    # keyed on mutation, the digest on content)
    m1._mutation += 1
    assert program_digest(m1) == program_digest(m2)
    # different content -> different digest
    m3, s3 = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(m3, s3):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        fluid.layers.mean(fluid.layers.fc(input=x, size=5))
    assert program_digest(m3) != program_digest(m1)


def test_stable_digest_sensitive_to_tail_and_extra():
    m, _, _ = _mlp()
    base = stable_digest(m, (("amp-off",),))
    assert base == stable_digest(m, (("amp-off",),))
    assert base != stable_digest(m, (("amp", "bfloat16"),))
    assert base != stable_digest(m, (("amp-off",),),
                                 extra=(("kind", "parallel_executor"),))


def test_stable_digest_follows_the_package_sources(monkeypatch):
    """The same program under other kernels or peepholes lowers to another
    executable: the digest carries a hash of the package's sources, so no
    salt has to be bumped by hand when the lowering changes."""
    from paddle_tpu.cache import keys

    m, _, _ = _mlp()
    base = stable_digest(m, ())
    assert keys.is_digest(keys.lowering_version())
    monkeypatch.setattr(keys, "_lowering_version", "0" * 64)
    assert stable_digest(m, ()) != base


_CHILD = """
import json, os
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import flags, monitor

flags.set("monitor", True)
monitor.reset()
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.layers.data(name="x", shape=[8], dtype="float32")
    loss = fluid.layers.mean(fluid.layers.fc(input=x, size=4))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(startup)
out, = exe.run(main, feed={"x": np.ones((4, 8), "float32")},
               fetch_list=[loss])
snap = monitor.registry().snapshot()
root = os.environ["FLAGS_compile_cache_dir"]
print(json.dumps({
    "digests": sorted(f[:-4] for f in os.listdir(root)
                      if f.endswith(".aot")),
    "misses": sum(v for k, v in snap.items()
                  if "compile_cache_misses_total" in k),
    "info": exe.compile_cache_info(),
    "loss": float(np.asarray(out).reshape(-1)[0]),
}))
"""


def test_digest_and_warm_start_stable_across_processes(tmp_path):
    """The two cross-process contracts at once: the same program in two
    processes (with DIFFERENT hash seeds — nothing in the key may lean on
    hash()) lands on the same L2 keys, and the second process compiles
    NOTHING (monitor misses == 0, every executable deserialized)."""
    script = tmp_path / "child.py"
    script.write_text(_CHILD)

    def run(hashseed):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONHASHSEED"] = hashseed
        env["PYTHONPATH"] = REPO
        env["FLAGS_compile_cache_dir"] = str(tmp_path / "store")
        proc = subprocess.run(
            [sys.executable, str(script)], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=240)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    cold = run("1")
    warm = run("2")
    assert cold["digests"], cold
    assert cold["digests"] == warm["digests"]
    assert cold["misses"] >= 1
    assert cold["info"]["l2"]["puts"] >= 1
    assert warm["misses"] == 0, warm
    assert warm["info"]["l2"]["hits"] >= 1, warm
    assert warm["loss"] == cold["loss"]


def test_flag_flip_changes_l2_key(tmp_path):
    """A config that changes the compiled step (amp here; zero1/autoshard/
    overlap ride the same key tail on the ParallelExecutor) must land on a
    NEW L2 digest, never reuse the stale executable."""
    from paddle_tpu import amp

    main, startup, loss = _mlp()
    feed = {"x": np.ones((4, 8), np.float32)}
    scope = fluid.Scope()
    with flags.flag_guard(compile_cache_dir=str(tmp_path)), \
            fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        before = {f for f in os.listdir(tmp_path) if f.endswith(".aot")}
        with amp.auto_cast():
            exe.run(main, feed=feed, fetch_list=[loss])
        after = {f for f in os.listdir(tmp_path) if f.endswith(".aot")}
    assert before
    assert after > before, (before, after)


def test_zero1_flag_flips_parallel_executor_l2_key(tmp_path):
    from paddle_tpu.parallel_executor import BuildStrategy, ParallelExecutor

    xs = np.random.RandomState(0).randn(8, 4).astype("float32")

    def run_once(sharded):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(input=x, size=3))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
            main.random_seed = startup.random_seed = 7
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor(fluid.CPUPlace()).run(startup)
            bs = BuildStrategy()
            bs.sharded_weight_update = sharded
            pe = ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                  main_program=main, build_strategy=bs)
            out, = pe.run([loss], feed={"x": xs})
        return float(np.asarray(out).reshape(-1)[0])

    with flags.flag_guard(compile_cache_dir=str(tmp_path)):
        run_once(False)
        plain = {f for f in os.listdir(tmp_path) if f.endswith(".aot")}
        run_once(True)
        sharded = {f for f in os.listdir(tmp_path) if f.endswith(".aot")}
    assert plain
    assert sharded > plain, (plain, sharded)


# ---------------------------------------------------------------------------
# fallbacks: corrupt / stale entries recompile, never raise
# ---------------------------------------------------------------------------

# the four ways a compiled step is run (tests/test_trace.py's list and the
# ParallelExecutor's scan): all take the one path through the cache
RUNNERS = ["executor", "executor_scan", "parallel_executor",
           "parallel_executor_scan"]


def _runner(runner, main, loss):
    """(target, run): the Executor or ParallelExecutor named, and one step
    of `main` on it (one scan of two where the name says so)."""
    import jax

    iters = 2 if runner.endswith("_scan") else None
    feed = {"x": np.ones((4, 8) if iters is None else (iters, 4, 8),
                         np.float32)}
    if runner.startswith("parallel_executor"):
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                    main_program=main,
                                    devices=jax.devices()[:4])
        return pe, lambda: pe.run([loss], feed=feed, iters=iters)
    exe = fluid.Executor(fluid.CPUPlace())
    return exe, lambda: exe.run(main, feed=feed, fetch_list=[loss],
                                iters=iters)


@pytest.mark.parametrize("runner", RUNNERS)
def test_corrupt_entry_falls_back_and_self_heals(tmp_path, runner):
    main, startup, loss = _mlp()
    scope = fluid.Scope()
    with flags.flag_guard(compile_cache_dir=str(tmp_path), monitor=True), \
            fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        exe, run = _runner(runner, main, loss)
        run()
        paths = [os.path.join(tmp_path, f) for f in os.listdir(tmp_path)
                 if f.endswith(".aot")]
        assert paths and exe.compile_cache_info()["l2"]["puts"] >= 1
        for p in paths:
            _flip_tail(p)
        # force the L1 miss -> L2 path a restarted process would take
        exe._compile_cache.clear()
        out2, = run()
        info = exe.compile_cache_info()
        snap = monitor.registry().snapshot()
    assert np.isfinite(np.asarray(out2)).all()  # recompiled, ran clean
    assert info["l2"]["fallbacks"] >= 1, info
    assert sum(v for k, v in snap.items()
               if "compile_cache_l2_fallbacks_total" in k) >= 1, snap
    # self-heal: the recompile re-put a valid entry over the corrupt one
    store = L2Store(str(tmp_path))
    assert any(store.get(e["digest"])[0] == "hit" for e in store.entries())


@pytest.mark.parametrize("runner", ["executor_scan",
                                    "parallel_executor_scan"])
def test_a_scan_names_the_written_var_the_scope_lacks_on_a_hit_too(runner):
    """A scan carries every persistable var the program writes, so each
    must be in the scope before it: the error names the var and the cure,
    from the split of the state that every call makes, also the call that
    finds its step in the cache."""
    main, startup, loss = _mlp()
    weight = main.global_block().all_parameters()[0].name
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        exe, run = _runner(runner, main, loss)
        run()
        hits = exe.compile_cache_info()["hits"]
        scope.erase(weight)
        for _ in range(2):
            with pytest.raises(ValueError, match=(
                    "missing: .*" + weight + ".*Run the startup program "
                    r"\(or one plain exe.run\) first")):
                run()
        assert exe.compile_cache_info()["hits"] == hits + 1


def test_store_version_mismatch_is_stale(tmp_path, monkeypatch):
    store = L2Store(str(tmp_path))
    digest = "d" * 64
    store.put(digest, b"payload-bytes")
    assert store.get(digest)[0] == "hit"
    import paddle_tpu.cache.store as store_mod

    real = store_mod.environment()
    monkeypatch.setattr(
        store_mod, "environment",
        lambda: ("other-jax", "other-jaxlib", "cpu", real[3]))
    outcome, payload, header = store.get(digest)
    assert outcome == "stale"
    assert payload is None
    assert header["jax"] != "other-jax"  # the REAL header survives for ls
    # same jax and jaxlib, another runtime build (libtpu): stale too
    monkeypatch.setattr(store_mod, "environment",
                        lambda: real[:3] + ("another libtpu build",))
    assert store.get(digest)[0] == "stale"


def test_store_corrupt_truncated_garbage_and_miss(tmp_path):
    store = L2Store(str(tmp_path))
    digest = "a" * 64
    store.put(digest, b"x" * 100)
    path = store.path_for(digest)
    _flip_tail(path, 4)  # payload bit-flip -> checksum mismatch
    assert store.get(digest)[0] == "corrupt"
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) // 2])  # torn write
    assert store.get(digest)[0] == "corrupt"
    with open(path, "wb") as f:
        f.write(b"not a cache entry")  # foreign debris
    assert store.get(digest)[0] == "corrupt"
    ents = store.entries()
    assert len(ents) == 1 and ents[0]["ok"] is False  # ls surfaces debris
    assert store.get("b" * 64)[0] == "miss"


def test_store_prune_is_mtime_lru_and_clear_empties(tmp_path):
    store = L2Store(str(tmp_path))
    for i, digest in enumerate(("a" * 64, "b" * 64, "c" * 64)):
        store.put(digest, bytes(100))
        os.utime(store.path_for(digest), (i, i))  # a oldest, c newest
    total = store.total_bytes()
    removed = store.prune(total - 1)
    assert removed == 1
    assert not os.path.exists(store.path_for("a" * 64))  # oldest went
    assert os.path.exists(store.path_for("c" * 64))
    assert store.prune(total) == 0  # already under the cap
    assert store.clear() == 2
    assert store.entries() == [] and store.total_bytes() == 0


# ---------------------------------------------------------------------------
# CLI: paddle_tpu cache ls | prune | clear
# ---------------------------------------------------------------------------

def test_cache_cli_ls_prune_clear(tmp_path, capsys):
    from paddle_tpu.cli import main as cli_main

    with flags.flag_guard(compile_cache_dir=""):
        assert cli_main(["cache", "ls"]) == 2  # no dir anywhere
    assert cli_main(["cache", "ls",
                     "--dir", str(tmp_path / "missing")]) == 2
    capsys.readouterr()

    store = L2Store(str(tmp_path))
    store.put("e" * 64, b"z" * 64, kind="executor")
    assert cli_main(["cache", "ls", "--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "e" * 16 in out and "executor" in out and "ok" in out

    assert cli_main(["cache", "ls", "--dir", str(tmp_path),
                     "--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["dir"] == str(tmp_path)
    assert data["total_bytes"] > 0
    assert data["entries"][0]["digest"] == "e" * 64
    assert data["entries"][0]["ok"] is True

    assert cli_main(["cache", "prune", "--dir", str(tmp_path),
                     "--max-mb", "1"]) == 0  # under the cap: keeps all
    assert os.path.exists(store.path_for("e" * 64))
    assert cli_main(["cache", "clear", "--dir", str(tmp_path)]) == 0
    assert store.entries() == []
    # the flag works as the default --dir
    with flags.flag_guard(compile_cache_dir=str(tmp_path)):
        assert cli_main(["cache", "ls"]) == 0


# ---------------------------------------------------------------------------
# monitor surface
# ---------------------------------------------------------------------------

def test_journal_summary_renders_l2_outcomes():
    records = [
        {"total_ms": 1.0, "cache": "miss", "cache_l2_fallback": "corrupt"},
        {"total_ms": 1.0, "cache": "hit", "cache_level": "l2"},
        {"total_ms": 1.0, "cache": "hit", "cache_level": "l1",
         "cache_evictions": 2},
    ]
    summary = monitor.summarize_journal(records)
    assert summary["cache"] == {"hit": 2, "miss": 1, "hit_l2": 1}
    assert summary["cache_evictions"] == 2
    assert summary["cache_l2_fallbacks"] == 1
    text = monitor.format_summary(summary)
    assert "2 hits / 1 misses" in text
    assert "1 persistent warm starts" in text
    assert "2 evictions" in text
    assert "1 L2 fallbacks" in text


# ---------------------------------------------------------------------------
# concurrent same-digest puts: atomic, last-writer-wins, counted
# ---------------------------------------------------------------------------

def test_concurrent_same_digest_puts_atomic_and_counted(tmp_path):
    """Regression (satellite): N writers committing the SAME digest must
    last-write-win atomically — a concurrent get() sees exactly one
    writer's whole entry, never a torn interleaving — and every overwrite
    is counted on compile_cache_l2_duplicate_puts_total."""
    import threading

    store = L2Store(str(tmp_path))
    digest = "f" * 64
    payload = b"q" * 4096
    with flags.flag_guard(monitor=True):
        store.put(digest, payload)  # seed: every racer below overwrites
        stop = threading.Event()
        bad = []

        def reader():
            while not stop.is_set():
                outcome, got, _header = store.get(digest)
                # atomic replace: the entry is always whole and valid
                if outcome != "hit" or got != payload:
                    bad.append(outcome)
                    return

        def writer():
            for _ in range(5):
                store.put(digest, payload)

        r = threading.Thread(target=reader)
        ws = [threading.Thread(target=writer) for _ in range(4)]
        r.start()
        for w in ws:
            w.start()
        for w in ws:
            w.join(30)
        stop.set()
        r.join(30)
        snap = monitor.registry().snapshot()
    assert bad == [], bad
    assert store.get(digest)[0] == "hit"
    # no tmp debris leaked from the 20 concurrent commits
    assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []
    dups = sum(v for k, v in snap.items()
               if "compile_cache_l2_duplicate_puts_total" in k)
    assert dups == 20, snap


def test_put_blob_validates_framing_digest_binding_and_checksum(tmp_path):
    """put_blob is the fetch_compiled commit path: it must re-validate a
    peer's blob (magic, framing, digest binding, payload checksum) before
    the atomic replace, so a corrupt or mislabeled publish can never
    poison the local cache."""
    src = L2Store(str(tmp_path / "src"))
    dst = L2Store(str(tmp_path / "dst"))
    digest = "a" * 64
    src.put(digest, b"payload" * 100)
    blob = src.read_blob(digest)
    assert blob is not None and blob.startswith(b"PTAC1\n")
    # a clean publish commits and reads back as a hit
    assert dst.put_blob(digest, blob) is True
    outcome, payload, _header = dst.get(digest)
    assert outcome == "hit" and payload == b"payload" * 100
    # mislabeled: blob's header digest != the digest it was offered under
    assert dst.put_blob("b" * 64, blob) is False
    assert dst.get("b" * 64)[0] == "miss"
    # payload corruption: checksum mismatch refuses the commit
    torn = blob[:-4] + bytes(b ^ 0xFF for b in blob[-4:])
    assert dst.put_blob(digest, torn) is False
    # foreign garbage: framing refuses it
    assert dst.put_blob(digest, b"not a cache entry") is False
    # the earlier good entry survived every refused commit
    assert dst.get(digest)[0] == "hit"


# ---------------------------------------------------------------------------
# distributed compile service (fetch_compiled RPC on the elastic master)
# ---------------------------------------------------------------------------

def test_compile_service_single_flight_lease_and_parked_fetch():
    import threading

    from paddle_tpu.parallel.master import MasterService

    svc = MasterService()
    digest = "c" * 64
    try:
        grants = []

        def racer():
            grants.append(svc.compiled_lease(digest)["granted"])

        ts = [threading.Thread(target=racer) for _ in range(5)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(10)
        assert sum(grants) == 1, grants  # single-flight: ONE compiler
        got = {}

        def parked():
            got["blob"] = svc.compiled_get(digest, wait_s=30.0)

        t = threading.Thread(target=parked)
        t.start()
        time.sleep(0.1)
        assert t.is_alive()  # parked on the leaseholder's publish
        svc.compiled_put(digest, b"ptac-blob")
        t.join(10)
        assert got["blob"] == b"ptac-blob"
        stats = svc.compiled_stats()
        assert stats["leases"] == 1 and stats["lease_rejects"] == 4
        assert stats["waits"] >= 1 and stats["active_leases"] == 0
        # a lease on a cached digest: fetch it, don't compile it
        assert svc.compiled_lease(digest) == {"granted": False,
                                              "cached": True}
        # a repeat publish is a duplicate (last writer wins)
        assert svc.compiled_put(digest, b"ptac-blob2")["duplicate"]
        assert svc.compiled_stats()["duplicate_puts"] == 1
    finally:
        svc.stop()


def test_compile_service_rejects_malformed_digest_not_connection():
    """A path-traversal-shaped digest rejects the OP, not the TCP
    connection: the same client keeps working after the refusal."""
    from paddle_tpu.parallel.master import MasterClient, MasterService
    from paddle_tpu.parallel.rpc import RpcError

    svc = MasterService()
    port = svc.serve()
    c = MasterClient(f"127.0.0.1:{port}")
    try:
        with pytest.raises(RpcError):
            c.compiled_get("../../etc/passwd")
        with pytest.raises(RpcError):
            c.compiled_lease("A" * 64)  # uppercase hex: refused
        assert c.compiled_stats()["entries"] == 0  # connection survived
    finally:
        c.close()
        svc.stop()


def test_remote_fetch_commits_to_local_l2_and_counts(tmp_path):
    """The executor-side client path end to end over TCP: a peer's
    published blob lands in the local L2 (remote hit), an unpublished
    digest wins the lease (remote miss -> compile here), and a
    mislabeled publish falls back instead of poisoning the cache."""
    from paddle_tpu.cache import service
    from paddle_tpu.parallel.master import MasterService

    svc = MasterService()
    port = svc.serve()
    payload = b"p" * 256
    digest = "c" * 64
    src = L2Store(str(tmp_path / "src"))
    src.put(digest, payload)
    blob = src.read_blob(digest)
    dst = L2Store(str(tmp_path / "dst"))
    cc = CompileCache("executor")
    try:
        with flags.flag_guard(compile_service=f"127.0.0.1:{port}",
                              compile_cache_dir=str(tmp_path / "dst"),
                              monitor=True):
            assert service.enabled()
            # the compiler's aot_sink side: publish the whole-file blob
            assert service.offer_blob(digest, blob) is True
            # the fetching replica's side: L2 miss -> remote hit
            assert cc._remote_fetch(digest, dst) == payload
            assert dst.get(digest)[0] == "hit"  # committed locally
            assert cc.l2_remote_hits == 1
            # nobody compiled this digest: we win the lease -> None
            assert cc._remote_fetch("d" * 64, dst) is None
            assert cc.l2_remote_misses == 1
            # a mislabeled publish: put_blob refuses, fallback counted
            svc.compiled_put("e" * 64, blob)
            assert cc._remote_fetch("e" * 64, dst) is None
            assert cc.l2_fallbacks == 1
            assert dst.get("e" * 64)[0] == "miss"  # never committed
            info = cc.info()["l2"]
            assert info["remote_hits"] == 1
            assert info["remote_misses"] == 1
            assert info["service"] == f"127.0.0.1:{port}"
            snap = monitor.registry().snapshot()
            assert sum(v for k, v in snap.items()
                       if "compile_cache_l2_remote_hits_total" in k) == 1
    finally:
        service.reset()
        svc.stop()


@pytest.mark.parametrize("runner", RUNNERS)
def test_l2_hit_journals_as_hit_with_cache_load_phase(tmp_path, runner):
    """An L2 warm start is a cache HIT in the journal (level "l2") with
    the deserialize time attributed to a cache_load phase, not compile."""
    main, startup, loss = _mlp()
    journal = tmp_path / "journal.jsonl"
    scope = fluid.Scope()
    with flags.flag_guard(compile_cache_dir=str(tmp_path / "store"),
                          monitor=True,
                          monitor_journal=str(journal)), \
            fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        exe, run = _runner(runner, main, loss)
        run()
        exe._compile_cache.clear()  # simulate the fresh-process L1 miss
        run()
    records = monitor.read_journal(str(journal))
    cold = records[-2]
    warm = records[-1]
    assert cold["cache"] == "miss" and "compile" in cold["phases_ms"]
    assert warm["cache"] == "hit", warm
    assert warm.get("cache_level") == "l2", warm
    assert "cache_load" in warm["phases_ms"], warm
    assert "compile" not in warm["phases_ms"], warm


# ---------------------------------------------------------------------------
# the compile key (executor_core.step_key): one case per site and ingredient
# ---------------------------------------------------------------------------
_COMMON = ("feed_shape", "fetch_list", "amp", "debug_nans", "wire",
           "donate_feeds", "health")
_KEY_CASES = ([("exe", i) for i in _COMMON]
              + [("scan", i) for i in _COMMON + ("iters",)]
              + [("pe", i) for i in _COMMON + ("iters", "zero1",
                                               "grad_scale", "autoshard")])
# what each ingredient is flipped to, from the base configuration below
_FLIPS = {
    "feed_shape": {"batch": 16},
    "fetch_list": {"fetch": ("loss", "pred")},
    "amp": {"amp": "bfloat16"},
    "debug_nans": {"debug_nans": True},
    "wire": {"wire": "affine"},        # from the cast-only wire, see below
    "donate_feeds": {"donate": True},
    "health": {"health": 1},
    "iters": {"iters": 3},
    "zero1": {"zero1": True},
    "grad_scale": {"gss": fluid.BuildStrategy.GradientScaleStrategy.One},
    "autoshard": {"autoshard": True},
}


def _key_net():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=8, act="relu")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.Momentum(learning_rate=0.01,
                                 momentum=0.9).minimize(loss)
    return main, startup, {"loss": loss.name, "pred": pred.name}


def _run_configured(target, main, names, cfg):
    """One run of `main` on `target` (an Executor or a ParallelExecutor)
    under the configuration `cfg`."""
    from paddle_tpu import amp
    from paddle_tpu.datapipe import WIRE_KEY, WireFormat, WireSpec
    from paddle_tpu.executor import _apply_debug_nans

    K, batch = cfg["iters"], cfg["batch"]
    lead = (batch,) if K is None else (K, batch)
    rs = np.random.RandomState(0)
    if cfg["wire"]:
        feed = {"x": rs.randint(0, 255, lead + (8,)).astype(np.uint8),
                WIRE_KEY: (WireSpec({"x": WireFormat("uint8")})
                           if cfg["wire"] == "cast"
                           else WireSpec.uint8_images("x"))}
    else:
        feed = {"x": rs.randn(*lead, 8).astype(np.float32)}
    feed["y"] = rs.randn(*lead, 1).astype(np.float32)
    fetch = [names[n] for n in cfg["fetch"]]
    if cfg["amp"]:
        amp.enable(cfg["amp"])
    try:
        with flags.flag_guard(debug_nans=cfg["debug_nans"],
                              health=cfg["health"]):
            if isinstance(target, fluid.ParallelExecutor):
                bs = target._build_strategy
                bs.sharded_weight_update = cfg["zero1"]
                bs.gradient_scale_strategy = cfg["gss"]
                bs.auto_sharding = cfg["autoshard"]
                target.run(fetch, feed=feed, iters=K,
                           donate_feeds=cfg["donate"])
            else:
                target.run(main, feed=feed, fetch_list=fetch, iters=K,
                           donate_feeds=cfg["donate"])
    finally:
        amp.disable()
        _apply_debug_nans()     # jax's own switch follows the flag back


@pytest.mark.parametrize("site,ingredient", _KEY_CASES,
                         ids=[f"{s}-{i}" for s, i in _KEY_CASES])
def test_step_key_ingredient_misses_then_hits(site, ingredient):
    """At each of the three run bodies (Executor single step, Executor
    iters=K, ParallelExecutor on the virtual mesh), flipping ONE
    trace-affecting input compiles exactly one more step (one more L1
    entry), and coming back to either configuration compiles none: the
    input is in the key, and nothing unstable is."""
    base = {"batch": 8, "fetch": ("loss",), "amp": None,
            "debug_nans": False, "wire": None, "donate": False,
            "health": 0, "iters": None if site == "exe" else 2,
            "zero1": False, "autoshard": False,
            "gss": fluid.BuildStrategy.GradientScaleStrategy.CoeffNumDevice}
    if ingredient == "wire":
        base["wire"] = "cast"   # uint8 feeds in both, only the spec differs
    flipped = dict(base, **_FLIPS[ingredient])
    main, startup, names = _key_net()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        target = exe if site != "pe" else fluid.ParallelExecutor(
            use_cuda=False, loss_name=names["loss"], main_program=main)

        def entries():
            return target.compile_cache_info()["entries"]

        n0 = entries()
        for cfg, want in ((base, 1), (flipped, 2), (base, 2), (flipped, 2)):
            _run_configured(target, main, names, cfg)
            assert entries() == n0 + want, (cfg, entries() - n0)


def test_step_key_content_is_stable_primitives():
    """The content part goes to the L2 digest as it is: no id(), no
    object, and the identity part alone names the program."""
    from paddle_tpu.core import executor_core

    main, _, names = _key_net()
    feed = {"x": np.zeros((4, 8), np.float32)}
    ident, content = executor_core.step_key(
        main, feed, [names["loss"]], ["w"], iters=2,
        extra=(("zero1", True, 0, 8),))
    assert ident == (id(main), main._mutation)

    def primitive(v):
        return v is None or isinstance(v, (str, int, float, bool)) or (
            isinstance(v, tuple) and all(primitive(e) for e in v))

    assert primitive(content)
    assert ("iters", 2) in content and ("zero1", True, 0, 8) in content
    assert stable_digest(main, content) == stable_digest(
        main.clone(), executor_core.step_key(
            main.clone(), feed, [names["loss"]], ["w"], iters=2,
            extra=(("zero1", True, 0, 8),))[1])
