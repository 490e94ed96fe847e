"""Command-line entry: `python -m paddle_tpu <command>`.

Reference parity: the `paddle` wrapper script (paddle/scripts/
submit_local.sh.in:1 — version/train subcommands that set up the cluster
env and exec the user script) and the flag listing the reference scatters
through gflags --help.

Commands:
  version            print version + backend info
  flags              list registered runtime flags (FLAGS_* env overrides)
  train SCRIPT ...   launch a training script with PADDLE_* cluster env
                     (--role trainer|pserver --trainers N --trainer-id I
                      --pservers host:port,...) — the same variables
                     Trainer()'s cluster bootstrap reads.
  monitor JOURNAL    summarize a FLAGS_monitor_journal step journal
                     (step/phase timings, compile-cache hit rate, replica
                     skew); --json emits the summary as JSON.
  health summary LEDGER
                     summarize a FLAGS_health_ledger run ledger (loss
                     curve, grad norms, detector events, divergence
                     step); --json emits the summary as JSON.
  health compare A B [--tol-final F] [--tol-traj F]
                     assert convergence parity between two run ledgers
                     (final-loss delta, step-aligned trajectory max
                     deviation, divergence-step agreement); rc 0 on
                     parity, 1 on a violated tolerance, 2 on an
                     unreadable ledger — the standard parity gate
                     bench.py and green_gate use.
  checkpoint inspect DIR [--serial N]
                     list a checkpoint directory's serials and their
                     commit status (committed / incomplete / orphaned
                     .tmp) and show the latest (or chosen) manifest,
                     including the ZeRO-1 shard layout (param -> shard
                     owner, shard bytes) when the run had FLAGS_zero1=1;
                     --json emits the report as JSON.
  serve --model-dir DIR [--http PORT | --selftest N]
                     serve a save_inference_model directory with the
                     batching engine (serve.Server): warm every batch
                     bucket, then either expose the stdlib HTTP frontend
                     (POST /v1/infer, GET /healthz /stats /metrics) or
                     fire N synthetic requests and print stats JSON.
  trace summary DIR  summarize a flight-recorder dump directory (span
                     counts per name, traces, slowest spans).
  trace dump [--out DIR] [--selftest]
                     dump the in-process flight recorder (--selftest
                     records synthetic spans first, proving the
                     record->dump->load path end to end).
  fleet replica --model-dir DIR [--port 0 --port-file F]
                     run one serving replica process for a fleet: the
                     serve engine behind its HTTP frontend, exiting 0
                     after a graceful drain (POST /admin/drain or
                     SIGTERM) with empty queues. --master registers a
                     TTL heartbeat with a parallel.master service;
                     --router registers with a fleet router over HTTP.
                     --chaos-kill-at/--chaos-hang-at N arm a
                     replica_kill/replica_hang fault on the Nth
                     executor dispatch (failover drills).
  elastic status --master HOST:PORT
                     membership snapshot of an elastic training job: the
                     current epoch, live world size and member names
                     (parallel.elastic; --json for machine parsing) —
                     the drill/runbook observability command.
  elastic drain NAME --master HOST:PORT
                     manually scale DOWN: remove worker NAME from the
                     membership so the survivors resize at their next
                     step boundary (the operator-driven twin of the
                     SIGTERM-drain path).
  fleet router [--replicas ep1,ep2,...] [--master HOST:PORT]
                     run the fleet router: health-checked least-queue
                     routing over the replica set with retry-on-other-
                     replica, deadlines, a fleet-wide retry budget and
                     graceful drain orchestration (POST /admin/drain
                     {"replica": name}).
"""

import argparse
import os
import sys


def _cmd_version(args):
    from . import __version__

    print(f"paddle_tpu {__version__}")
    try:
        import jax

        devs = jax.devices()
        print(f"jax {jax.__version__}; {len(devs)} device(s): "
              f"{devs[0].platform}")
    except Exception as e:  # jax may be unusable in a build sandbox
        print(f"jax unavailable: {e}")
    return 0


def _cmd_flags(args):
    from . import flags

    for name, (value, type_, help_) in flags.all_flags().items():
        print(f"FLAGS_{name} ({type_}, current={value}): {help_}")
    return 0


def _cmd_monitor(args):
    import glob as globmod

    from .monitor import format_summary, read_journal, summarize_journal

    paths = []
    for pat in args.journal:
        hits = sorted(globmod.glob(pat))
        paths.extend(hits or [pat])
    journals = {}
    for path in paths:
        if path in journals:
            continue
        try:
            journals[path] = read_journal(path)
        except OSError as e:
            print(f"cannot read journal: {e}", file=sys.stderr)
            return 1
    if len(journals) == 1:
        summary = summarize_journal(next(iter(journals.values())))
        if args.json:
            import json

            print(json.dumps(summary, indent=2))
        else:
            print(format_summary(summary))
        return 0

    # several journals = one per fleet process: per-process summaries
    # plus the obs clock-aligned merge (same-host processes share the
    # epoch clock, so offset 0 per journal) for cross-replica skew
    import json
    import os as osmod

    from .obs import merge_step_timeline

    summaries = {p: summarize_journal(r) for p, r in journals.items()}
    merged = merge_step_timeline(
        [{"name": osmod.path.basename(p) or p, "journal": r,
          "offset_s": 0.0} for p, r in journals.items()])
    if args.json:
        print(json.dumps({"journals": summaries,
                          "fleet": {k: merged[k] for k in
                                    ("steps", "stragglers")}}, indent=2))
        return 0
    hdr = (f"{'journal':<28}{'steps':>7}{'mean_ms':>10}{'p50_ms':>9}"
           f"{'p95_ms':>9}{'cache_hit%':>11}")
    print(hdr)
    print("-" * len(hdr))
    for path, s in summaries.items():
        ms = s.get("step_ms") or {}
        cache = s.get("cache") or {}
        lookups = (cache.get("hit") or 0) + (cache.get("miss") or 0)
        hit = 100.0 * (cache.get("hit") or 0) / lookups if lookups \
            else None
        print(f"{osmod.path.basename(path) or path:<28.27}"
              f"{s.get('steps', 0):>7}"
              f"{_opt_num(ms.get('mean')):>10}"
              f"{_opt_num(ms.get('p50')):>9}"
              f"{_opt_num(ms.get('p95')):>9}"
              f"{_opt_num(hit):>11}")
    steps = merged["steps"]
    if steps:
        worst = max(steps, key=lambda s: s["skew_ms"])
        print(f"fleet: {len(steps)} step(s) aligned across processes; "
              f"max skew {worst['skew_ms']:.1f} ms at step "
              f"{worst['step']} (slowest {worst['slowest']})")
        for name, run in sorted(merged["stragglers"].items()):
            print(f"straggler: {name} slowest on {run} consecutive "
                  f"step(s)")
    else:
        print("fleet: no step overlap between the journals")
    return 0


def _opt_num(v, spec="{:.1f}"):
    return "-" if v is None else spec.format(v)


def _cmd_health(args):
    import json

    from .health import compare as hcompare
    from .health.ledger import read_ledger

    if args.health_action == "summary":
        try:
            records = read_ledger(args.ledger)
        except OSError as e:
            print(f"cannot read ledger: {e}", file=sys.stderr)
            return 2
        summary = hcompare.summarize_ledger(records)
        if args.json:
            print(json.dumps(summary, indent=2))
        else:
            print(hcompare.format_ledger_summary(summary))
        return 0
    if args.health_action == "compare":
        try:
            a = read_ledger(args.a)
            b = read_ledger(args.b)
        except OSError as e:
            print(f"cannot read ledger: {e}", file=sys.stderr)
            return 2
        report = hcompare.compare_ledgers(
            a, b, tol_final=args.tol_final, tol_traj=args.tol_traj)
        if args.json:
            print(json.dumps(report, indent=2))
        else:
            print(hcompare.format_compare(report))
        return 0 if report["ok"] else 1
    return 2


def _fmt_age(seconds):
    s = float(seconds)
    if s < 60:
        return f"{s:.0f}s"
    if s < 3600:
        return f"{s / 60:.0f}m"
    if s < 86400:
        return f"{s / 3600:.1f}h"
    return f"{s / 86400:.1f}d"


def _cmd_cache(args):
    import json

    from . import flags
    from .cache import L2Store

    root = args.dir or flags.get("compile_cache_dir")
    if not root:
        print("no cache dir: pass --dir or set FLAGS_compile_cache_dir",
              file=sys.stderr)
        return 2
    if not os.path.isdir(root):
        print(f"not a directory: {root}", file=sys.stderr)
        return 2
    store = L2Store(root)
    if args.cache_action == "ls":
        ents = store.entries()
        if args.json:
            print(json.dumps({
                "dir": root,
                "total_bytes": sum(e["bytes"] for e in ents),
                "entries": ents,
            }, indent=2))
            return 0
        if not ents:
            print(f"{root}: empty")
            return 0
        print(f"{'digest':<18} {'kind':<20} {'bytes':>10} {'age':>7} "
              f"{'jaxlib':<12} status")
        for e in ents:
            print(f"{e['digest'][:16] + '..':<18} "
                  f"{e.get('kind', '?'):<20} {e['bytes']:>10} "
                  f"{_fmt_age(e['age_s']):>7} {e.get('jaxlib', '?'):<12} "
                  f"{'ok' if e['ok'] else 'CORRUPT'}")
        total = sum(e["bytes"] for e in ents)
        print(f"{len(ents)} entries, {total / 1e6:.1f} MB in {root}")
        return 0
    if args.cache_action == "prune":
        max_mb = args.max_mb if args.max_mb is not None \
            else flags.get("compile_cache_dir_max_mb")
        removed = store.prune(int(max_mb) * (1 << 20))
        print(f"pruned {removed} entries "
              f"({store.total_bytes() / 1e6:.1f} MB resident, "
              f"cap {max_mb} MB)")
        return 0
    if args.cache_action == "clear":
        removed = store.clear()
        print(f"removed {removed} entries from {root}")
        return 0
    return 2


def _cmd_checkpoint(args):
    from .resilience import inspect_dir

    try:
        report = inspect_dir(args.dir, serial=args.serial)
    except (OSError, ValueError) as e:
        print(f"cannot inspect checkpoint dir: {e}", file=sys.stderr)
        return 1
    if args.json:
        import json

        print(json.dumps(report, indent=2))
        return 0
    print(f"checkpoint dir: {report['checkpoint_dir']}")
    if not report["serials"]:
        print("  (no checkpoints)")
        return 0
    for ent in report["serials"]:
        print(f"  {ent['dir']:<24} serial={ent['serial']!s:<6} "
              f"{ent['status']:<12} {ent['bytes']} bytes")
    print(f"latest committed serial: {report['latest']}")
    manifest = report.get("manifest")
    if manifest:
        print(f"manifest (serial {manifest.get('serial')}): "
              f"format={manifest.get('format')} step={manifest.get('step')}")
        var_names = sorted((manifest.get("vars") or {}).keys())
        print(f"  vars ({len(var_names)}): {', '.join(var_names[:8])}"
              + (" ..." if len(var_names) > 8 else ""))
        dp = manifest.get("datapipe")
        if dp:
            print(f"  datapipe: {dp}")
        mesh = manifest.get("mesh")
        if mesh:
            mesh_s = "×".join(f"{k}={v}" for k, v in mesh.items())
            print(f"  mesh geometry: [{mesh_s}] (dp may change across a "
                  f"restore; other axes must match the target mesh)")
        el = (manifest.get("extra") or {}).get("elastic")
        if el:
            print(f"  elastic resize point: epoch={el.get('epoch')} "
                  f"world_size={el.get('world_size')} "
                  f"members={el.get('members')}")
        zero1 = manifest.get("zero1")
        if zero1:
            print(f"  zero1 shard layout ({len(zero1)} sharded params; "
                  f"checkpoint stores canonical full layout):")
            for pname in sorted(zero1):
                ent = zero1[pname]
                owners = ent.get("owners") or {}
                own = ", ".join(
                    f"dp{r}:[{owners[r][0]}:{owners[r][1]})"
                    for r in sorted(owners, key=int)[:4])
                if len(owners) > 4:
                    own += ", ..."
                print(f"    {pname}: shape={ent.get('shape')} "
                      f"shards={ent.get('num_shards')}x"
                      f"{ent.get('shard_numel')} "
                      f"param_shard={ent.get('param_shard_bytes')}B "
                      f"accum_shard={ent.get('accum_shard_bytes')}B")
                print(f"      owners: {own}")
                accs = ent.get("accums") or []
                if accs:
                    print(f"      accums: {', '.join(accs)}")
        ashard = manifest.get("autoshard")
        if ashard:
            mesh = ashard.get("mesh_axes") or {}
            mesh_s = "×".join(f"{k}={v}" for k, v in mesh.items())
            params = ashard.get("params") or {}
            print(f"  autoshard plan digest={ashard.get('digest')} "
                  f"mesh[{mesh_s}] layout={ashard.get('layout', 'full')} "
                  f"({len(params)} sharded params; checkpoint stores "
                  f"canonical full layout):")
            for pname in sorted(params):
                spec = ", ".join(str(a) for a in params[pname])
                print(f"    {pname}: ({spec})")
        pp = manifest.get("pipeline")
        if pp:
            print(f"  pipeline: stages={pp.get('stages')} "
                  f"axis={pp.get('axis', 'pp')} "
                  f"microbatches={pp.get('microbatches')} "
                  f"schedule={pp.get('schedule', '1f1b')} "
                  f"plan digest={pp.get('digest')} (params stored full; "
                  f"restore requires a matching pp axis size)")
    elif report.get("format"):
        print(f"legacy io-format checkpoint (no manifest); files: "
              f"{len(report.get('files', []))}")
    return 0


def _shard_demo_program():
    """Small embedding+fc net with mp seeds on the embedding table and the
    first fc weight — the same shape of model the autoshard dryrun and
    bench A/B use."""
    import paddle_tpu as fluid

    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        ids = fluid.layers.data(name="ids", shape=[1], dtype="int64")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        emb = fluid.layers.embedding(ids, size=[32, 16])
        h = fluid.layers.fc(emb, 32, act="relu")
        p = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    gb = main.global_block()
    embw = next(n for n, v in gb.vars.items()
                if getattr(v, "persistable", False) and v.shape == (32, 16))
    w1 = next(n for n, v in gb.vars.items()
              if getattr(v, "persistable", False) and v.shape == (16, 32))
    fluid.parallel.set_sharding(gb.var(embw), ("mp", None))
    fluid.parallel.set_sharding(gb.var(w1), (None, "mp"))
    return main


def _cmd_shard(args):
    import json

    from .parallel import autoshard

    mesh_axes = {}
    for part in (args.mesh or "").split(","):
        if not part.strip():
            continue
        k, _, v = part.partition("=")
        try:
            mesh_axes[k.strip()] = int(v)
        except ValueError:
            print(f"bad --mesh entry {part!r} (want name=size)",
                  file=sys.stderr)
            return 1
    if not mesh_axes:
        print("empty --mesh", file=sys.stderr)
        return 1
    if args.shard_action == "search":
        return _cmd_shard_search(args, mesh_axes)
    seeds = {}
    for s in args.seed or []:
        name, _, spec_s = s.partition("=")
        seeds[name.strip()] = tuple(
            None if e.strip() in ("", "None", "none", "-") else e.strip()
            for e in spec_s.split(","))
    if args.selftest:
        program = _shard_demo_program()
    elif args.model_dir:
        from .core.framework import Program

        with open(os.path.join(args.model_dir, "__model__")) as f:
            payload = json.load(f)
        program = Program.from_dict(payload["program"])
    else:
        print("shard plan needs --model-dir or --selftest", file=sys.stderr)
        return 1
    try:
        plan = autoshard.build_plan(program, mesh_axes,
                                    batch_axis=args.batch_axis,
                                    extra_seeds=seeds or None)
    except (TypeError, ValueError) as e:
        print(f"shard plan error: {e}", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(plan.describe(), indent=2))
    else:
        print(plan.render(verbose=not args.quiet))
    ok = plan.is_total() and not plan.unresolved
    if args.selftest:
        ok = ok and len(plan.sharded_names()) > 0
        # stderr so --json stdout stays machine-parseable
        print(f"shard plan selftest: {'OK' if ok else 'FAILED'}",
              file=sys.stderr if args.json else sys.stdout)
    return 0 if ok else 2


def _cmd_shard_search(args, mesh_axes):
    """`shard search`: enumerate seed placements, score whole plans with
    the unified cost model, report the cheapest vs the manual seeds.
    rc 0 search ok, 1 plan/search error, 2 selftest contract violated."""
    import json

    from .parallel import autoshard

    if args.selftest:
        program = _shard_demo_program()
    elif args.model_dir:
        loaded = _load_saved_program(args.model_dir)
        if isinstance(loaded, str):
            print(loaded, file=sys.stderr)
            return 1
        program = loaded[0]
    else:
        print("shard search needs --model-dir or --selftest",
              file=sys.stderr)
        return 1
    try:
        res = autoshard.search_plan(
            program, mesh_axes, batch_axis=args.batch_axis,
            batch_size=args.batch, hbm_budget=args.hbm_budget,
            max_params=args.max_params, rounds=args.rounds)
    except (TypeError, ValueError) as e:
        print(f"shard search error: {e}", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(res.to_dict(), indent=2))
    else:
        print(res.render())
        if not args.quiet:
            print(res.plan.render(verbose=False))
    ok = res.plan.is_total() and not res.plan.unresolved \
        and res.cost["score_s"] <= res.manual_cost["score_s"]
    if args.selftest:
        # the searched plan must never lose to the manual seeds, and the
        # demo net must actually end up sharded
        ok = ok and len(res.plan.sharded_names()) > 0
        print(f"shard search selftest: {'OK' if ok else 'FAILED'}",
              file=sys.stderr if args.json else sys.stdout)
    return 0 if ok else 2


def _check_demo_program():
    """Small MLP training program for `check --selftest`."""
    import paddle_tpu as fluid

    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, 16, act="relu")
        p = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, ["x", "y"], [loss.name]


def _cmd_check(args):
    import json

    from . import analysis

    mesh_axes = None
    if args.mesh:
        mesh_axes = {}
        for part in args.mesh.split(","):
            if not part.strip():
                continue
            k, _, v = part.partition("=")
            try:
                mesh_axes[k.strip()] = int(v)
            except ValueError:
                print(f"bad --mesh entry {part!r} (want name=size)",
                      file=sys.stderr)
                return 2

    if args.selftest:
        # 1) a well-formed training program must verify clean ...
        prog, feeds, fetches = _check_demo_program()
        clean = analysis.verify(prog, level="full", feed_names=feeds,
                                fetch_names=fetches, mesh_axes=mesh_axes,
                                context="check --selftest")
        # 2) ... and the SAME program with an op knocked out must not:
        # drop the first fc's matmul, leaving its output undefined
        broken = prog.clone()
        ops = broken.global_block().ops
        del ops[next(i for i, op in enumerate(ops) if op.type == "mul")]
        bad = analysis.verify(broken, level="full", feed_names=feeds,
                              fetch_names=fetches, mesh_axes=mesh_axes,
                              context="check --selftest (broken)")
        ok = clean.ok and not bad.ok and "PTA001" in bad.codes()
        if args.json:
            print(json.dumps({"ok": ok, "clean": clean.to_dict(),
                              "broken": bad.to_dict()}, indent=2))
        else:
            print(clean.render(verbose=not args.quiet))
            print("--- intentionally broken program (must flag PTA001) ---")
            print(bad.render(verbose=not args.quiet))
            print(f"check selftest: {'OK' if ok else 'FAILED'}")
        return 0 if ok else 1

    if not args.model_dir:
        print("check needs --model-dir or --selftest", file=sys.stderr)
        return 2
    from .core.framework import Program

    model_path = os.path.join(args.model_dir, "__model__")
    try:
        with open(model_path) as f:
            payload = json.load(f)
    except (OSError, ValueError) as e:
        print(f"cannot load {model_path}: {e}", file=sys.stderr)
        return 2
    program = Program.from_dict(payload["program"])
    report = analysis.verify(
        program, level=args.level,
        feed_names=payload.get("feed_var_names"),
        fetch_names=payload.get("fetch_var_names"),
        mesh_axes=mesh_axes, context=f"check {args.model_dir}")
    if args.json:
        print(json.dumps(report.to_dict(), indent=2))
    else:
        print(report.render(verbose=not args.quiet))
    return report.rc


def _load_saved_program(model_dir):
    """(program, feed_names, fetch_names) from a save_inference_model dir,
    or an error string."""
    import json

    from .core.framework import Program

    model_path = os.path.join(model_dir, "__model__")
    try:
        with open(model_path) as f:
            payload = json.load(f)
    except (OSError, ValueError) as e:
        return f"cannot load {model_path}: {e}"
    return (Program.from_dict(payload["program"]),
            payload.get("feed_var_names"),
            payload.get("fetch_var_names"))


def _seed_cycle(program):
    """Clone with a genuine def-use cycle appended (two scale ops reading
    each other's outputs) — the `analyze graph --selftest` mutation."""
    from .core.framework import OP_ROLE_ATTR_NAME, OpRole

    clone = program.clone()
    gb = clone.global_block()
    for nm in ("a_cyc", "b_cyc"):
        gb.create_var(name=nm, shape=[1], dtype="float32")
    role = {OP_ROLE_ATTR_NAME: int(OpRole.Forward), "scale": 1.0}
    gb.append_op(type="scale", inputs={"X": ["b_cyc"]},
                 outputs={"Out": ["a_cyc"]}, attrs=dict(role))
    gb.append_op(type="scale", inputs={"X": ["a_cyc"]},
                 outputs={"Out": ["b_cyc"]}, attrs=dict(role))
    return clone


def _seed_gather_rewire(program):
    """Clone of a zero1-rewritten program whose first zero1_gather is
    rewired to consume the PRE-update param shard — flat index order stays
    valid (PTA012-clean) but the gather no longer consumes the update, the
    dependence-path divergence only PTA033 sees."""
    clone = program.clone()
    gb = clone.global_block()
    gat = next(op for op in gb.ops if op.type == "zero1_gather")
    pupd = gat.input("X")[0]
    gat.rename_input(pupd, pupd.replace("@zero1_upd", "@zero1_shard"))
    clone._mutation += 1
    return clone


def _cmd_analyze(args):
    import json

    from .analysis import (ProgramVerificationError, Report, dataflow,
                           schedule)

    mesh_axes = None
    if getattr(args, "mesh", None):
        mesh_axes = {}
        for part in args.mesh.split(","):
            if not part.strip():
                continue
            k, _, v = part.partition("=")
            try:
                mesh_axes[k.strip()] = int(v)
            except ValueError:
                print(f"bad --mesh entry {part!r} (want name=size)",
                      file=sys.stderr)
                return 2

    def _resolve_program():
        """(program, feeds) for the non-selftest path, honoring --zero1."""
        if not args.model_dir:
            print(f"analyze {args.analyze_action} needs --model-dir or "
                  f"--selftest", file=sys.stderr)
            return None
        loaded = _load_saved_program(args.model_dir)
        if isinstance(loaded, str):
            print(loaded, file=sys.stderr)
            return None
        program, feeds, _ = loaded
        if args.zero1:
            from .parallel import zero1 as _z1
            program, _ = _z1.apply(program, args.zero1)
        return program, feeds

    if args.analyze_action == "pipeline":
        return _cmd_analyze_pipeline(args)

    if args.analyze_action == "graph":
        if args.selftest:
            prog, feeds, _ = _check_demo_program()
            if args.zero1:
                from .parallel import zero1 as _z1
                prog, _ = _z1.apply(prog, args.zero1)
            graph = dataflow.build_graph(prog, feed_names=feeds)
            clean = Report(level="full", context="analyze graph --selftest")
            dataflow.check_hazards(prog, clean, feed_names=feeds,
                                   graph=graph)
            seeded = Report(level="full",
                            context="analyze graph --selftest (cyclic)")
            dataflow.check_hazards(_seed_cycle(prog), seeded,
                                   feed_names=feeds)
            ok = clean.ok and not graph.has_cycle \
                and not seeded.ok and "PTA030" in seeded.codes()
            if args.json:
                print(json.dumps({"ok": ok, "graph": graph.summary(),
                                  "clean": clean.to_dict(),
                                  "seeded": seeded.to_dict()}, indent=2))
            else:
                print(f"graph: {graph.summary()}")
                print(clean.render(verbose=not args.quiet))
                print("--- seeded cyclic clone (must flag PTA030) ---")
                print(seeded.render(verbose=not args.quiet))
                print(f"analyze graph selftest: {'OK' if ok else 'FAILED'}")
            return 0 if ok else 1
        resolved = _resolve_program()
        if resolved is None:
            return 2
        program, feeds = resolved
        report = Report(level="full",
                        context=f"analyze graph {args.model_dir}")
        graph = dataflow.check_hazards(program, report, feed_names=feeds)
        if args.json:
            print(json.dumps({"graph": graph.summary(),
                              "report": report.to_dict()}, indent=2))
        else:
            print(f"graph: {graph.summary()}")
            print(report.render(verbose=not args.quiet))
        return report.rc

    # analyze schedule
    if args.selftest:
        from .parallel import zero1 as _z1
        prog, feeds, _ = _check_demo_program()
        parts = args.zero1 or (mesh_axes or {}).get("dp", 8)
        z, _zplan = _z1.apply(prog, parts)
        sched = schedule.analyze(
            z, mesh_axes=mesh_axes or {"dp": parts}, feed_names=feeds,
            batch_size=args.batch, bucket_bytes=args.bucket_bytes)
        reordered, plan = schedule.apply_plan(z, sched.plan,
                                              feed_names=feeds)
        ok = sched.critical_path_ms > 0 and len(plan.buckets) > 0 \
            and len(plan.moves) > 0 and reordered is not z
        # the seeded divergence must be REJECTED, never silently scheduled
        rejected = False
        codes = []
        try:
            schedule.analyze(_seed_gather_rewire(z),
                             mesh_axes=mesh_axes or {"dp": parts},
                             feed_names=feeds)
        except ProgramVerificationError as e:
            rejected = True
            codes = sorted(e.report.codes())
        ok = ok and rejected and "PTA033" in codes
        if args.json:
            print(json.dumps({"ok": ok, "schedule": sched.to_dict(),
                              "seeded_rejected": rejected,
                              "seeded_codes": codes}, indent=2))
        else:
            print(sched.render())
            print(f"--- seeded gather-rewire clone: "
                  f"{'rejected ' + str(codes) if rejected else 'NOT rejected'}"
                  f" ---")
            print(f"analyze schedule selftest: {'OK' if ok else 'FAILED'}")
        return 0 if ok else 1

    resolved = _resolve_program()
    if resolved is None:
        return 2
    program, feeds = resolved
    try:
        sched = schedule.analyze(
            program, mesh_axes=mesh_axes, feed_names=feeds,
            batch_size=args.batch, bucket_bytes=args.bucket_bytes)
    except ProgramVerificationError as e:
        print(e.report.render(verbose=not args.quiet), file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(sched.to_dict(), indent=2))
    else:
        print(sched.render())
    return 0


def _pipeline_demo_program():
    """Fixed-name 3-layer MLP trainer for `analyze pipeline --selftest` —
    explicit layer names so two builds yield identical param names (and
    therefore identical startup init) for the parity comparison."""
    import paddle_tpu as fluid

    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, 32, act="relu", name="pls1")
        h = fluid.layers.fc(h, 16, act="relu", name="pls2")
        p = fluid.layers.fc(h, 1, name="pls3")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, start, ["x", "y"], loss.name


def _cmd_analyze_pipeline(args):
    """`analyze pipeline`: partition a program over the pp axis, verify
    the split (PTA040/041), and report the 1F1B schedule + bubble.

    --selftest additionally 1F1B-executes the demo net at the requested
    stage count, asserts bitwise loss parity against an unpartitioned
    (n_stages=1) replay with identical microbatching, asserts the
    structural bubble equals the analytic (p-1)/(m+p-1) bound, and
    asserts a seeded backwards-edge mutation is REFUSED with PTA040.
    rc 0 ok, 1 contract violated / illegal split, 2 usage error."""
    import json

    import numpy as np

    from .analysis import ProgramVerificationError, Report
    from .parallel import pipeline as pl

    p, m = args.stages, args.microbatches
    if p < 1 or m < 1:
        print("--stages and --microbatches must be >= 1", file=sys.stderr)
        return 2

    if args.selftest:
        from .core.scope import Scope
        from .executor import Executor

        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(4 * m, 16).astype(np.float32),
                "y": rng.randn(4 * m, 1).astype(np.float32)}

        def run(n_stages):
            main, start, feeds, loss_name = _pipeline_demo_program()
            scope = Scope()
            Executor().run(start, scope=scope)
            runner = pl.PipelineRunner(
                main, n_stages, loss_name=loss_name, feed_names=feeds,
                n_microbatches=m, scope=scope, batch_size=4 * m)
            reports = [runner.run(feed) for _ in range(2)]
            return [np.asarray(r["loss"]) for r in reports], reports[-1]

        ref_losses, _ = run(1)
        losses, rep = run(p)
        parity = all((a == b).all() for a, b in zip(ref_losses, losses))
        bound = pl.analytic_bubble(p, m)
        bubble_ok = rep["bubble_fraction"] <= bound + 1e-9

        # a split that sends forward data to an EARLIER stage must be
        # refused, never silently executed (mirrors analyze schedule)
        main, start, feeds, loss_name = _pipeline_demo_program()
        plan = pl.partition(main, max(2, p), feed_names=feeds,
                            batch_size=4 * m)
        # force a forward def-use edge to run BACKWARDS: producer (the
        # first matmul) onto the last stage, its consumer onto stage 0
        ops = main.global_block().ops
        u = min(i for i, op in enumerate(ops)
                if plan.phases[i] == pl.PHASE_FWD and op.type == "mul")
        outs = set(ops[u].output_arg_names())
        v = min(i for i, op in enumerate(ops)
                if i > u and plan.phases[i] == pl.PHASE_FWD
                and outs & set(op.input_arg_names()))
        plan.assignment[u] = plan.n_stages - 1
        plan.assignment[v] = 0
        rejected, codes = False, []
        try:
            pl.build_stage_programs(main, plan, feed_names=feeds,
                                    fetch_names=[loss_name])
        except ProgramVerificationError as e:
            rejected = True
            codes = sorted(e.report.codes())
        ok = parity and bubble_ok and rejected and "PTA040" in codes
        result = {
            "ok": ok,
            "parity_bitwise": parity,
            "bubble_fraction": rep["bubble_fraction"],
            "bubble_analytic": bound,
            "bubble_measured": rep["bubble_measured"],
            "n_stages": p, "n_microbatches": m,
            "seeded_rejected": rejected, "seeded_codes": codes,
            "plan": rep["plan"],
        }
        if args.json:
            print(json.dumps(result, indent=2))
        else:
            print(f"pipeline: {p} stages x {m} microbatches  "
                  f"bubble {rep['bubble_fraction']:.4f} "
                  f"(analytic {bound:.4f}, measured "
                  f"{rep['bubble_measured']:.4f})")
            print(f"  bitwise loss parity vs n_stages=1: {parity}")
            print(f"--- seeded backwards-edge clone: "
                  f"{'rejected ' + str(codes) if rejected else 'NOT rejected'}"
                  f" ---")
            print(f"analyze pipeline selftest: {'OK' if ok else 'FAILED'}")
        return 0 if ok else 1

    if not args.model_dir:
        print("analyze pipeline needs --model-dir or --selftest",
              file=sys.stderr)
        return 2
    loaded = _load_saved_program(args.model_dir)
    if isinstance(loaded, str):
        print(loaded, file=sys.stderr)
        return 2
    program, feeds, _ = loaded
    try:
        plan = pl.partition(program, p, feed_names=feeds,
                            batch_size=args.batch)
    except ValueError as e:
        print(f"analyze pipeline error: {e}", file=sys.stderr)
        return 2
    report = Report(level="full",
                    context=f"analyze pipeline {args.model_dir}")
    pl.check_partition(program, plan, report, feed_names=feeds)
    sim = pl.simulate_schedule(pl.schedule_1f1b(p, m))
    out = {
        "plan": plan.to_dict(),
        "bubble_analytic": pl.analytic_bubble(p, m),
        "bubble_fraction": sim["bubble_fraction"],
        "n_microbatches": m,
        "report": report.to_dict(),
    }
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        print(plan.describe())
        print(f"  1F1B x {m} microbatches: bubble "
              f"{sim['bubble_fraction']:.4f} "
              f"(analytic {out['bubble_analytic']:.4f})")
        print(report.render(verbose=not args.quiet))
    return report.rc


def _cmd_serve(args):
    import json

    import numpy as np

    from . import flags
    from .core.places import CPUPlace, TPUPlace
    from .serve import ServeConfig, Server
    from .serve.http import serve_http

    if args.cache_dir:
        # persistent compile cache: bucket warmup deserializes executables
        # another process already compiled (sub-second warm start)
        flags.set("compile_cache_dir", args.cache_dir)
    place = CPUPlace() if args.place == "cpu" else TPUPlace(0)
    config = ServeConfig(
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        replicas=args.replicas, slo_ms=args.slo_ms,
        max_queue_rows=args.max_queue_rows)
    try:
        server = Server.from_inference_model(
            args.model_dir, place=place, config=config)
    except (OSError, ValueError) as e:
        print(f"cannot load inference model: {e}", file=sys.stderr)
        return 1
    server.start()
    print(f"ready: buckets={list(server.config.buckets)} "
          f"replicas={config.replicas} "
          f"warm_compiles={server._warm_entries}", file=sys.stderr)
    if args.http is not None:
        print(f"http frontend on {args.host}:{args.http}", file=sys.stderr)
        serve_http(server, host=args.host, port=args.http)
        return 0
    # selftest: synthetic single-example requests from the feed shapes,
    # a handful of concurrent clients so the batcher actually batches
    import threading

    n, per = args.selftest, max(1, args.selftest // 8)
    rng = np.random.RandomState(0)

    def fire(k):
        for _ in range(k):
            feed = {name: rng.standard_normal(
                server._example_shape(name)).astype(
                server._feed_dtype(name))
                for name in server.feed_names}
            server.submit(feed).result()

    threads = [threading.Thread(target=fire, args=(per,))
               for _ in range(-(-n // per))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = server.stats()
    server.stop()
    print(json.dumps(stats, indent=2))
    return 0 if stats["steady_state_compiles"] == 0 else 1


def _cmd_fleet_replica(args):
    import json
    import signal
    import threading

    from . import flags
    from .core.places import CPUPlace, TPUPlace
    from .serve import ServeConfig, Server
    from .serve.http import make_http_server

    if args.cache_dir:
        # fleet spin-up: every replica shares one persistent compile
        # cache, so only the first one ever compiles each bucket
        flags.set("compile_cache_dir", args.cache_dir)
    if args.compile_service:
        # ... or each replica has its own cache and the first MISSER
        # compiles while the rest fetch the blob by digest
        flags.set("compile_service", args.compile_service)
    if args.chaos_kill_at is not None or args.chaos_hang_at is not None \
            or args.chaos_delay_ms is not None:
        from .resilience import chaos

        monkey = chaos.ChaosMonkey()
        if args.chaos_kill_at is not None:
            monkey.add(chaos.Fault("replica_kill", at=args.chaos_kill_at))
        if args.chaos_hang_at is not None:
            monkey.add(chaos.Fault("replica_hang", at=args.chaos_hang_at,
                                   times=args.chaos_hang_times,
                                   delay_ms=args.chaos_hang_ms))
        if args.chaos_delay_ms is not None:
            # every dispatch: a deterministic per-batch service-time
            # floor -> replica capacity ~= 1000/delay_ms batches/s on
            # any host, which makes autoscale drills reproducible
            monkey.add(chaos.Fault("delay", at=0, times=1 << 62,
                                   delay_ms=args.chaos_delay_ms))
        chaos.install(monkey)
    place = CPUPlace() if args.place == "cpu" else TPUPlace(0)
    config = ServeConfig(
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        replicas=args.replicas, max_queue_rows=args.max_queue_rows,
        slo_ms=args.slo_ms)
    try:
        server = Server.from_inference_model(
            args.model_dir, place=place, config=config)
    except (OSError, ValueError) as e:
        print(f"cannot load inference model: {e}", file=sys.stderr)
        return 1
    server.start()
    # a drained replica's frontend shuts itself down -> serve_forever
    # returns -> this process exits 0: the rolling-restart contract
    httpd = make_http_server(server, host=args.host, port=args.port,
                             shutdown_on_drain=True)
    port = httpd.server_address[1]
    endpoint = f"{args.host}:{port}"
    name = args.name or f"replica-{port}"
    if args.port_file:
        with open(args.port_file, "w") as f:
            f.write(f"{port}\n")
    print(f"replica {name} serving on {endpoint}", file=sys.stderr)

    obs_client = None
    if args.obs:
        from . import obs as obs_mod

        obs_client = obs_mod.maybe_start("replica", replica=name,
                                         endpoint=args.obs)

    heartbeater = None
    if args.master:
        from .parallel.master import Heartbeater, MasterClient

        heartbeater = Heartbeater(MasterClient(args.master), "serve",
                                  name, endpoint, ttl=args.ttl)
        heartbeater.start()
    elif args.router:
        import http.client

        def _register_loop():
            body = json.dumps({"name": name, "endpoint": endpoint})
            while not server._stop:
                try:
                    host, rport = args.router.rsplit(":", 1)
                    conn = http.client.HTTPConnection(host, int(rport),
                                                      timeout=2.0)
                    try:
                        conn.request("POST", "/admin/register", body=body)
                        conn.getresponse().read()
                    finally:
                        conn.close()
                except OSError:
                    pass  # router restart: next beat re-registers
                stop_beats.wait(max(0.5, args.ttl / 3.0))

        stop_beats = threading.Event()
        threading.Thread(target=_register_loop, name="fleet-register",
                         daemon=True).start()

    def _sigterm(signum, frame):
        # SIGTERM = drain, not die: finish the backlog, THEN stop the
        # HTTP loop — same ordering as /admin/drain's shutdown_on_drain
        # path, so serve_forever() only returns once the queue is empty
        # (shutting down concurrently would snapshot stats mid-drain and
        # fail still-queued requests in the server.stop() below)
        def _drain_then_exit():
            server.drain()
            httpd.shutdown()

        threading.Thread(target=_drain_then_exit, name="serve-drain-sig",
                         daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        server.drain()
    finally:
        httpd.server_close()
        if heartbeater is not None:
            heartbeater.close()
    stats = server.stats()
    server.stop()
    if obs_client is not None:
        # final push AFTER stop: the collector sees the terminal journal
        # tail and any shutdown trace dump
        obs_client.stop()
    leftover = stats["queue_rows"]
    print(f"replica {name} exiting: drained queue_rows={leftover}",
          file=sys.stderr)
    return 0 if leftover == 0 else 1


def _cmd_fleet_router(args):
    from .serve.fleet import FleetConfig, Router, serve_fleet

    replicas = {}
    if args.replicas:
        for i, ep in enumerate(e for e in args.replicas.split(",") if e):
            replicas[f"r{i}"] = ep
    discover = None
    if args.master:
        from .parallel.master import MasterClient

        client = MasterClient(args.master)
        discover = lambda: client.lookup("serve")  # noqa: E731
    if not replicas and discover is None:
        print("router needs --replicas and/or --master", file=sys.stderr)
        return 1
    config = FleetConfig(
        probe_interval_s=args.probe_interval,
        request_deadline_ms=args.deadline_ms,
        attempt_timeout_ms=args.attempt_timeout_ms,
        max_attempts=args.max_attempts, hedge_ms=args.hedge_ms)
    router = Router(replicas, config=config, discover=discover)
    obs_client = None
    if args.obs:
        from . import obs as obs_mod

        obs_client = obs_mod.maybe_start("router", endpoint=args.obs)
    autoscaler = None
    if args.autoscale_model_dir:
        from .cache import place_jax_cache
        from .serve.fleet import (Autoscaler, AutoscalerConfig,
                                  ProcessReplicaSpawner)

        # port files and the per-replica L2 stores live under the same
        # root as JAX's own cache, named by this router's listen address:
        # a restarted router finds its replicas' caches again
        workdir = os.path.join(place_jax_cache(), "fleet_autoscale",
                               f"{args.host}_{args.port}")
        argv_base = [sys.executable, "-m", "paddle_tpu", "fleet",
                     "replica", "--model-dir", args.autoscale_model_dir,
                     "--place", "cpu", "--port", "0"]
        if args.compile_service:
            argv_base += ["--compile-service", args.compile_service]
        if args.autoscale_cache_dir:
            argv_base += ["--cache-dir", args.autoscale_cache_dir]
        spawner = ProcessReplicaSpawner(
            argv_base, workdir,
            per_replica_cache=not args.autoscale_cache_dir)
        autoscaler = Autoscaler(router, spawner, AutoscalerConfig(
            target_p99_ms=args.autoscale_target_p99_ms,
            high_queue_rows=args.autoscale_queue_rows,
            min_replicas=args.autoscale_min,
            max_replicas=args.autoscale_max,
            interval_s=args.autoscale_interval,
            cooldown_out_s=args.autoscale_cooldown_out,
            cooldown_in_s=args.autoscale_cooldown_in)).start()
    print(f"fleet router on {args.host}:{args.port} over "
          f"{sorted(replicas.values()) or 'master-discovered replicas'}",
          file=sys.stderr)
    serve_fleet(router, host=args.host, port=args.port)
    if autoscaler is not None:
        autoscaler.stop()
        autoscaler.spawner.stop_all()
    if obs_client is not None:
        obs_client.stop()
    return 0


def _cmd_elastic(args):
    import json

    from .parallel import elastic as elastic_mod

    if args.elastic_action == "status":
        try:
            st = elastic_mod.fetch_status(args.master, timeout=args.timeout)
        except OSError as e:
            print(f"cannot reach master {args.master}: {e}",
                  file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(st, indent=2))
        else:
            print(f"elastic job at {st['endpoint']}: epoch={st['epoch']} "
                  f"world_size={st['world_size']}")
            for name, addr in sorted(st["members"].items()):
                print(f"  {name}" + (f"  {addr}" if addr else ""))
        return 0
    if args.elastic_action == "drain":
        from .parallel.master import MasterClient

        client = MasterClient(args.master, connect_timeout=args.timeout)
        try:
            r = client.elastic_leave(args.name)
        except OSError as e:
            print(f"cannot reach master {args.master}: {e}",
                  file=sys.stderr)
            return 1
        finally:
            client.close()
        print(f"drained {args.name}: membership epoch now {r['epoch']} "
              f"(survivors resize at their next step boundary)")
        return 0
    return 1


def _cmd_fleet(args):
    if args.fleet_action == "replica":
        return _cmd_fleet_replica(args)
    if args.fleet_action == "router":
        return _cmd_fleet_router(args)
    return 1


def _cmd_obs(args):
    import json

    from . import obs as obs_mod

    if args.obs_action == "collect":
        import threading

        col = obs_mod.Collector(ttl_s=args.ttl,
                                straggler_ratio=args.straggler_ratio,
                                straggler_steps=args.straggler_steps)
        for target in args.scrape or []:
            name, _, endpoint = target.rpartition("=")
            col.add_scrape_target(name or endpoint, endpoint)
        httpd = obs_mod.make_obs_http(col, host=args.host, port=args.port)
        port = httpd.server_address[1]
        if args.port_file:
            with open(args.port_file, "w") as f:
                f.write(f"{port}\n")
        print(f"obs collector on {args.host}:{port} "
              f"(POST /v1/obs/push, GET /metrics /v1/obs/summary "
              f"/v1/obs/timeline; {len(args.scrape or [])} scrape "
              f"target(s))", file=sys.stderr)
        stop = threading.Event()
        if args.scrape:
            col.scrape_tick()

            def _scrape_loop():
                while not stop.wait(args.scrape_interval):
                    col.scrape_tick()

            threading.Thread(target=_scrape_loop, name="obs-scrape",
                             daemon=True).start()
        try:
            httpd.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            stop.set()
            httpd.server_close()
        return 0

    if args.obs_action == "top":
        return obs_mod.run_top(
            args.collector, interval_s=args.interval, once=args.once,
            json_out=args.json, iterations=args.iterations)

    if args.obs_action == "timeline":
        from .trace import load_dump

        dump_dirs = []        # [(lane name or None, dir)]
        merged_steps = None
        if args.collector:
            import http.client

            try:
                host, port = args.collector.rsplit(":", 1)
                conn = http.client.HTTPConnection(host, int(port),
                                                  timeout=5.0)
                try:
                    conn.request("GET", "/v1/obs/timeline")
                    resp = conn.getresponse()
                    body = resp.read()
                    if resp.status != 200:
                        raise OSError(f"HTTP {resp.status}")
                finally:
                    conn.close()
                tl = json.loads(body)
            except (OSError, ValueError) as e:
                print(f"cannot reach collector {args.collector}: {e}",
                      file=sys.stderr)
                return 2
            merged_steps = tl.get("timeline")
            dump_dirs.extend((d.get("replica"), d["dir"])
                             for d in tl.get("dumps", []))
        for d in args.dump or []:
            dump_dirs.append((None, d))
        if not dump_dirs and merged_steps is None:
            print("obs timeline needs --collector and/or --dump",
                  file=sys.stderr)
            return 2
        dumps, names = [], []
        for lane, d in dump_dirs:
            try:
                dumps.append(load_dump(d))
            except (OSError, ValueError) as e:
                print(f"skipping dump {d}: {e}", file=sys.stderr)
                continue
            names.append(lane or os.path.basename(d.rstrip("/")))
        if merged_steps is not None:
            print(obs_mod.format_timeline(merged_steps))
        if dumps:
            trace = obs_mod.merge_chrome_traces(dumps, names=names)
            lanes = {e['pid'] for e in trace['traceEvents']}
            print(f"merged trace: {len(dumps)} dump(s), "
                  f"{len(lanes)} pid lane(s), "
                  f"{sum(1 for e in trace['traceEvents'] if e['ph'] == 'X')}"
                  f" span event(s)")
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(trace, f)
                print(f"wrote {args.out}")
        elif args.out:
            print("no trace dumps to merge (nothing written)",
                  file=sys.stderr)
            return 1
        return 0
    return 1


def _cmd_trace(args):
    import json

    from . import trace

    if args.trace_action == "summary":
        try:
            loaded = trace.load_dump(args.dir)
        except (OSError, ValueError) as e:
            print(f"cannot load dump: {e}", file=sys.stderr)
            return 1
        man, spans = loaded["manifest"], loaded["spans"]
        print(f"dump: {args.dir}")
        print(f"  reason={man.get('reason')} format={man.get('format')} "
              f"spans={len(spans)} dropped={man.get('dropped')} "
              f"traces={man.get('traces')}")
        by_name = {}
        for sp in spans:
            agg = by_name.setdefault(sp["name"], [0, 0.0])
            agg[0] += 1
            agg[1] += sp["t1"] - sp["t0"]
        print(f"  {'span':<24} {'count':>6} {'total_ms':>10} {'avg_ms':>9}")
        for name, (n, tot) in sorted(by_name.items(),
                                     key=lambda kv: -kv[1][1]):
            print(f"  {name:<24} {n:>6} {tot * 1e3:>10.2f} "
                  f"{tot * 1e3 / n:>9.3f}")
        slow = sorted(spans, key=lambda s: s["t0"] - s["t1"])[:5]
        print("  slowest spans:")
        for sp in slow:
            print(f"    {(sp['t1'] - sp['t0']) * 1e3:>9.2f} ms  "
                  f"{sp['name']}  trace={sp['trace'][:8]} "
                  f"thread={sp.get('thread')}")
        return 0

    if args.trace_action == "dump":
        from . import flags

        if args.selftest:
            import time

            flags.set("trace", True)
            with trace.span("selftest.root", kind="selftest"):
                t0 = time.perf_counter()
                with trace.span("selftest.child", n=1):
                    pass
                trace.record("selftest.retro", t0, time.perf_counter())
        if not trace.enabled():
            print("tracing is off (FLAGS_trace=0) — nothing recorded",
                  file=sys.stderr)
            return 1
        path = trace.dump(reason="manual", out_dir=args.out)
        spans, dropped = trace.snapshot()
        print(f"dump written: {path} ({len(spans)} spans, "
              f"{dropped} dropped)")
        if args.selftest:
            loaded = trace.load_dump(path)
            names = {sp["name"] for sp in loaded["spans"]}
            want = {"selftest.root", "selftest.child", "selftest.retro"}
            if not want <= names:
                print(f"selftest FAILED: missing {want - names}",
                      file=sys.stderr)
                return 1
            print("selftest ok: record -> dump -> load round-trip")
        return 0
    return 1


def _cmd_train(args):
    env = dict(os.environ)
    env["PADDLE_TRAINING_ROLE"] = args.role.upper()
    env["PADDLE_TRAINERS"] = str(args.trainers)
    env["PADDLE_TRAINER_ID"] = str(args.trainer_id)
    if args.pservers:
        env["PADDLE_PSERVERS"] = args.pservers
    if args.current_endpoint:
        env["PADDLE_CURRENT_ENDPOINT"] = args.current_endpoint
    cmd = [sys.executable, args.script] + args.script_args
    os.execve(sys.executable, cmd, env)


def main(argv=None):
    parser = argparse.ArgumentParser(prog="paddle_tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    sub.add_parser("version", help="print version and backend info")
    sub.add_parser("flags", help="list runtime flags")

    m = sub.add_parser("monitor", help="summarize step-journal files "
                                       "(FLAGS_monitor_journal)")
    m.add_argument("journal", nargs="+",
                   help="JSONL step journal path(s); globs OK. Several "
                        "journals render a per-process comparison table "
                        "plus the clock-aligned cross-replica skew/"
                        "straggler merge")
    m.add_argument("--json", action="store_true",
                   help="emit the summary as JSON instead of a table")

    h = sub.add_parser("health", help="model-health run ledgers: "
                                      "summarize and assert convergence "
                                      "parity")
    hsub = h.add_subparsers(dest="health_action", required=True)
    hs = hsub.add_parser("summary", help="summarize a FLAGS_health_ledger "
                                         "run ledger")
    hs.add_argument("ledger", help="path of the JSONL health ledger")
    hs.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of a table")
    hc = hsub.add_parser("compare", help="convergence parity between two "
                                         "run ledgers (rc 0 parity / "
                                         "1 fail / 2 unreadable)")
    hc.add_argument("a", help="reference run ledger")
    hc.add_argument("b", help="candidate run ledger")
    hc.add_argument("--tol-final", type=float, default=1e-3,
                    help="max |final loss A - final loss B| at the last "
                         "common sampled step")
    hc.add_argument("--tol-traj", type=float, default=5e-3,
                    help="max step-aligned |loss A - loss B| over all "
                         "common sampled steps")
    hc.add_argument("--json", action="store_true",
                    help="emit the parity report as JSON")

    ca = sub.add_parser("cache", help="persistent compile-cache store "
                                      "(FLAGS_compile_cache_dir)")
    casub = ca.add_subparsers(dest="cache_action", required=True)
    cal = casub.add_parser("ls", help="list entries: digest, kind, bytes, "
                                      "age, jaxlib version")
    cal.add_argument("--dir", default=None,
                     help="store directory (default "
                          "FLAGS_compile_cache_dir)")
    cal.add_argument("--json", action="store_true",
                     help="emit the listing as JSON")
    cap_ = casub.add_parser("prune", help="delete oldest-used entries "
                                          "until the store fits the cap")
    cap_.add_argument("--dir", default=None,
                      help="store directory (default "
                           "FLAGS_compile_cache_dir)")
    cap_.add_argument("--max-mb", type=int, default=None,
                      help="size cap in MiB (default "
                           "FLAGS_compile_cache_dir_max_mb)")
    cac = casub.add_parser("clear", help="delete every entry")
    cac.add_argument("--dir", default=None,
                     help="store directory (default "
                          "FLAGS_compile_cache_dir)")

    c = sub.add_parser("checkpoint", help="inspect checkpoint directories")
    csub = c.add_subparsers(dest="checkpoint_action", required=True)
    ci = csub.add_parser("inspect", help="list serials, commit status and "
                                         "the manifest of a checkpoint dir")
    ci.add_argument("dir", help="checkpoint directory "
                                "(holds checkpoint_<N> subdirs)")
    ci.add_argument("--serial", type=int, default=None,
                    help="show this serial's manifest instead of the latest")
    ci.add_argument("--json", action="store_true",
                    help="emit the report as JSON")

    sh = sub.add_parser("shard", help="autoshard: GSPMD-style sharding "
                                      "plans over a program")
    shsub = sh.add_subparsers(dest="shard_action", required=True)
    shp = shsub.add_parser("plan", help="propagate seeds and render the "
                                        "total ShardingPlan with per-edge "
                                        "estimated reshard bytes")
    shp.add_argument("--model-dir", default=None,
                     help="save_inference_model directory to plan")
    shp.add_argument("--selftest", action="store_true",
                     help="build a small embedding+fc demo net, plan it, "
                          "and verify the plan is total")
    shp.add_argument("--mesh", default="dp=4,mp=2",
                     help="mesh axes as name=size pairs (plan construction "
                          "is analytic — no devices needed)")
    shp.add_argument("--seed", action="append", metavar="NAME=SPEC",
                     help="extra seed annotation, e.g. fc_0.w_0=None,mp "
                          "(repeatable; entries are axis names or None)")
    shp.add_argument("--batch-axis", default="dp",
                     help="mesh axis seeded onto data vars' dim 0")
    shp.add_argument("--json", action="store_true",
                     help="emit plan.describe() as JSON")
    shp.add_argument("--quiet", action="store_true",
                     help="summary and edges only, no per-var table")
    shse = shsub.add_parser(
        "search", help="search candidate seed placements across the mesh "
                       "axes and keep the whole-plan cheapest (unified "
                       "compute + collective-bytes + peak-HBM cost model)")
    shse.add_argument("--model-dir", default=None,
                      help="save_inference_model directory to search")
    shse.add_argument("--selftest", action="store_true",
                      help="search the embedding+fc demo net and verify "
                           "the searched plan never costs more than the "
                           "manual seeds")
    shse.add_argument("--mesh", default="dp=4,mp=2",
                      help="mesh axes as name=size pairs")
    shse.add_argument("--batch-axis", default="dp",
                      help="mesh axis seeded onto data vars' dim 0")
    shse.add_argument("--batch", type=int, default=8,
                      help="batch size substituted for dynamic dims in "
                           "the cost model")
    shse.add_argument("--hbm-budget", type=int, default=None,
                      metavar="BYTES",
                      help="per-replica peak-HBM feasibility budget; "
                           "plans over it are penalized out")
    shse.add_argument("--max-params", type=int, default=8,
                      help="search seed placements for the N largest "
                           "params")
    shse.add_argument("--rounds", type=int, default=2,
                      help="greedy coordinate-descent passes")
    shse.add_argument("--json", action="store_true",
                      help="emit the search result as JSON")
    shse.add_argument("--quiet", action="store_true",
                      help="skip the winning plan's summary render")

    ck = sub.add_parser("check", help="static program verification: graph/"
                                      "safety/sharding checks and the "
                                      "peak-HBM estimate (docs/analysis.md)")
    ck.add_argument("--model-dir", default=None,
                    help="save_inference_model directory to verify")
    ck.add_argument("--level", default="full", choices=["basic", "full"],
                    help="basic: structure + shape contracts; full: adds "
                         "safety/sharding checks and the HBM table")
    ck.add_argument("--mesh", default=None, metavar="NAME=SIZE,...",
                    help="mesh axes for the sharding checks and per-replica "
                         "HBM accounting, e.g. dp=4,mp=2")
    ck.add_argument("--selftest", action="store_true",
                    help="verify a clean demo program AND an intentionally "
                         "broken clone (must flag PTA001); rc 0 when both "
                         "behave")
    ck.add_argument("--json", action="store_true",
                    help="emit the report as JSON")
    ck.add_argument("--quiet", action="store_true",
                    help="show errors only, not warnings")

    an = sub.add_parser("analyze", help="SSA dataflow graph, PTA03x hazard "
                                        "detection, and the static overlap "
                                        "schedule (docs/analysis.md)")
    ansub = an.add_subparsers(dest="analyze_action", required=True)
    ag = ansub.add_parser("graph", help="build the SSA def-use dependency "
                                        "graph and run the dataflow hazard "
                                        "detector (PTA030-PTA034)")
    ag.add_argument("--model-dir", default=None,
                    help="save_inference_model directory to analyze")
    ag.add_argument("--zero1", type=int, default=0, metavar="N",
                    help="apply the ZeRO-1 rewrite with N shards before "
                         "analyzing")
    ag.add_argument("--selftest", action="store_true",
                    help="analyze a clean demo training program AND a "
                         "seeded cyclic clone (must flag PTA030); rc 0 "
                         "when both behave")
    ag.add_argument("--json", action="store_true",
                    help="emit the graph summary and report as JSON")
    ag.add_argument("--quiet", action="store_true",
                    help="show errors only, not warnings")
    asch = ansub.add_parser(
        "schedule", help="critical path over the analytic cost models and "
                         "the bucketed reduce-scatter overlap plan")
    asch.add_argument("--model-dir", default=None,
                      help="save_inference_model directory to schedule")
    asch.add_argument("--mesh", default="dp=8", metavar="NAME=SIZE,...",
                      help="mesh axes for the ring collective-bytes model")
    asch.add_argument("--zero1", type=int, default=0, metavar="N",
                      help="apply the ZeRO-1 rewrite with N shards before "
                           "scheduling")
    asch.add_argument("--batch", type=int, default=1,
                      help="batch size substituted for dynamic dims in the "
                           "FLOPs model")
    asch.add_argument("--bucket-bytes", type=int, default=None,
                      help="override FLAGS_overlap_bucket_bytes for the "
                           "gradient-bucketing plan")
    asch.add_argument("--selftest", action="store_true",
                      help="schedule a zero1-rewritten demo program (must "
                           "hoist a non-empty bucket plan) AND verify a "
                           "seeded collective-order divergence is rejected "
                           "with PTA033; rc 0 when both behave")
    asch.add_argument("--json", action="store_true",
                      help="emit the schedule report as JSON")
    asch.add_argument("--quiet", action="store_true",
                      help="show errors only, not warnings")
    apl = ansub.add_parser(
        "pipeline", help="pp-axis stage partition (parallel.pipeline): "
                         "min-cut plan, PTA040/041 legality, and the 1F1B "
                         "schedule's bubble fraction")
    apl.add_argument("--model-dir", default=None,
                     help="save_inference_model directory to partition")
    apl.add_argument("--stages", type=int, default=2,
                     help="pipeline stage count (pp axis size)")
    apl.add_argument("--microbatches", type=int, default=4,
                     help="1F1B microbatches per step")
    apl.add_argument("--batch", type=int, default=1,
                     help="batch size substituted for dynamic dims in the "
                          "FLOPs/bytes models")
    apl.add_argument("--selftest", action="store_true",
                     help="1F1B-execute the demo net (bitwise loss parity "
                          "vs unpartitioned, bubble <= analytic bound) AND "
                          "verify a seeded backwards-edge split is refused "
                          "with PTA040; rc 0 when all hold")
    apl.add_argument("--json", action="store_true",
                     help="emit the report as JSON")
    apl.add_argument("--quiet", action="store_true",
                     help="show errors only, not warnings")

    s = sub.add_parser("serve", help="serve a saved inference model with "
                                     "the batching engine")
    s.add_argument("--model-dir", required=True,
                   help="save_inference_model directory")
    s.add_argument("--place", default="tpu", choices=["tpu", "cpu"])
    s.add_argument("--max-batch", type=int, default=8)
    s.add_argument("--max-wait-ms", type=float, default=2.0)
    s.add_argument("--replicas", type=int, default=1)
    s.add_argument("--slo-ms", type=float, default=None)
    s.add_argument("--max-queue-rows", type=int, default=None)
    s.add_argument("--http", type=int, default=None, metavar="PORT",
                   help="expose the HTTP frontend on PORT (blocking)")
    s.add_argument("--host", default="127.0.0.1")
    s.add_argument("--selftest", type=int, default=64, metavar="N",
                   help="without --http: fire N synthetic requests from "
                        "concurrent clients and print stats JSON")
    s.add_argument("--cache-dir", default=None,
                   help="persistent compile-cache directory "
                        "(FLAGS_compile_cache_dir): warmup loads "
                        "executables compiled by earlier processes")

    tr = sub.add_parser("trace", help="flight-recorder dumps")
    trsub = tr.add_subparsers(dest="trace_action", required=True)
    trs = trsub.add_parser("summary", help="summarize a flight-recorder "
                                           "dump directory")
    trs.add_argument("dir", help="dump directory (holds manifest.json)")
    trd = trsub.add_parser("dump", help="dump the in-process flight "
                                        "recorder")
    trd.add_argument("--out", default=None,
                     help="output base dir (default FLAGS_trace_dump_dir "
                          "or cwd)")
    trd.add_argument("--selftest", action="store_true",
                     help="record synthetic spans first and verify the "
                          "dump loads back")

    f = sub.add_parser("fleet", help="multi-replica serving: replica and "
                                     "router processes")
    fsub = f.add_subparsers(dest="fleet_action", required=True)
    fr = fsub.add_parser("replica", help="run one serving replica process "
                                         "(drains clean on /admin/drain "
                                         "or SIGTERM)")
    fr.add_argument("--model-dir", required=True,
                    help="save_inference_model directory")
    fr.add_argument("--place", default="cpu", choices=["tpu", "cpu"])
    fr.add_argument("--host", default="127.0.0.1")
    fr.add_argument("--port", type=int, default=0,
                    help="HTTP port (0 = ephemeral; see --port-file)")
    fr.add_argument("--port-file", default=None,
                    help="write the bound port here once listening")
    fr.add_argument("--name", default=None,
                    help="replica name (default replica-<port>)")
    fr.add_argument("--max-batch", type=int, default=8)
    fr.add_argument("--max-wait-ms", type=float, default=2.0)
    fr.add_argument("--replicas", type=int, default=1,
                    help="engine executor replicas inside this process")
    fr.add_argument("--max-queue-rows", type=int, default=None)
    fr.add_argument("--slo-ms", type=float, default=None,
                    help="latency SLO; violations count "
                         "serve_slo_violations_total and trigger "
                         "flight-recorder dumps under FLAGS_trace")
    fr.add_argument("--router", default=None, metavar="HOST:PORT",
                    help="register with this fleet router over HTTP")
    fr.add_argument("--master", default=None, metavar="HOST:PORT",
                    help="heartbeat a parallel.master TTL registration")
    fr.add_argument("--ttl", type=float, default=10.0,
                    help="registration TTL seconds")
    fr.add_argument("--chaos-kill-at", type=int, default=None, metavar="N",
                    help="SIGKILL this replica on its Nth executor "
                         "dispatch (failover drill)")
    fr.add_argument("--chaos-hang-at", type=int, default=None, metavar="N",
                    help="hang this replica on its Nth executor dispatch")
    fr.add_argument("--chaos-hang-ms", type=float, default=None,
                    help="hang duration (default: effectively forever)")
    fr.add_argument("--chaos-hang-times", type=int, default=1,
                    metavar="K",
                    help="hang on K consecutive dispatches from "
                         "--chaos-hang-at (straggler drills)")
    fr.add_argument("--chaos-delay-ms", type=float, default=None,
                    help="sleep this long on EVERY executor dispatch: a "
                         "deterministic service-time floor, so capacity "
                         "drills (the green_gate autoscale drill) see "
                         "the same queueing on any host")
    fr.add_argument("--obs", default=None, metavar="HOST:PORT",
                    help="push metrics/journal/trace snapshots to this "
                         "obs collector (see `paddle_tpu obs collect`)")
    fr.add_argument("--cache-dir", default=None,
                    help="persistent compile-cache directory shared by "
                         "the fleet (FLAGS_compile_cache_dir): only the "
                         "first replica compiles, the rest deserialize")
    fr.add_argument("--compile-service", default=None, metavar="HOST:PORT",
                    help="distributed compile service (a parallel.master "
                         "with compiled_* ops, FLAGS_compile_service): on "
                         "an L2 miss, fetch the serialized executable by "
                         "digest instead of compiling — scale-out warm "
                         "start with compile_cache_misses == 0. Needs "
                         "--cache-dir")
    fo = fsub.add_parser("router", help="run the fleet router over a "
                                        "replica set")
    fo.add_argument("--replicas", default="",
                    help="comma-separated replica host:port list")
    fo.add_argument("--master", default=None, metavar="HOST:PORT",
                    help="discover replicas from a parallel.master "
                         "registry (kind=serve)")
    fo.add_argument("--host", default="127.0.0.1")
    fo.add_argument("--port", type=int, default=8100)
    fo.add_argument("--probe-interval", type=float, default=0.5)
    fo.add_argument("--deadline-ms", type=float, default=30000.0,
                    help="per-request routing deadline")
    fo.add_argument("--attempt-timeout-ms", type=float, default=None,
                    help="per-attempt transport timeout")
    fo.add_argument("--max-attempts", type=int, default=3)
    fo.add_argument("--hedge-ms", type=float, default=None,
                    help="hedge a silent first attempt after this long")
    fo.add_argument("--obs", default=None, metavar="HOST:PORT",
                    help="push router metrics to this obs collector")
    fo.add_argument("--autoscale-model-dir", default=None, metavar="DIR",
                    help="enable the autoscaler: spawn `fleet replica` "
                         "processes serving this save_inference_model "
                         "dir when the latency target breaches, drain "
                         "them away when load calms")
    fo.add_argument("--autoscale-min", type=int, default=1,
                    help="autoscaler floor (replicas)")
    fo.add_argument("--autoscale-max", type=int, default=4,
                    help="autoscaler ceiling (replicas)")
    fo.add_argument("--autoscale-target-p99-ms", type=float, default=500.0,
                    help="windowed router p99 the autoscaler holds")
    fo.add_argument("--autoscale-queue-rows", type=float, default=None,
                    help="queued rows across the fleet that also arm "
                         "scale-out")
    fo.add_argument("--autoscale-interval", type=float, default=1.0,
                    help="control-loop tick seconds")
    fo.add_argument("--autoscale-cooldown-out", type=float, default=5.0,
                    help="seconds between scale-outs")
    fo.add_argument("--autoscale-cooldown-in", type=float, default=30.0,
                    help="seconds between scale-ins")
    fo.add_argument("--autoscale-cache-dir", default=None,
                    help="shared --cache-dir for spawned replicas "
                         "(default: per-replica dirs under a temp "
                         "workdir — with --compile-service, warm start "
                         "then rides fetch_compiled, not the filesystem)")
    fo.add_argument("--compile-service", default=None, metavar="HOST:PORT",
                    help="pass through to spawned replicas so scale-out "
                         "warm-starts from peers' compiles")

    ob = sub.add_parser("obs", help="fleet-wide observability: collector "
                                    "sink, live top table, merged "
                                    "timeline")
    obsub = ob.add_subparsers(dest="obs_action", required=True)
    obc = obsub.add_parser("collect", help="run the fleet collector "
                                           "(push sink + scrape poller + "
                                           "aggregated /metrics)")
    obc.add_argument("--host", default="127.0.0.1")
    obc.add_argument("--port", type=int, default=9200,
                     help="HTTP port (0 = ephemeral; see --port-file)")
    obc.add_argument("--port-file", default=None,
                     help="write the bound port here once listening")
    obc.add_argument("--ttl", type=float, default=None,
                     help="stale-process expiry seconds "
                          "(default FLAGS_obs_ttl_s)")
    obc.add_argument("--scrape", action="append", default=None,
                     metavar="[NAME=]HOST:PORT",
                     help="poll this /metrics exposition as a fleet "
                          "member (repeatable)")
    obc.add_argument("--scrape-interval", type=float, default=2.0)
    obc.add_argument("--straggler-ratio", type=float, default=1.2,
                     help="slowest/median step-time ratio that counts "
                          "toward straggler attribution")
    obc.add_argument("--straggler-steps", type=int, default=3,
                     help="consecutive slowest steps before "
                          "fleet_straggler{replica=} fires")
    obt = obsub.add_parser("top", help="live fleet table over the "
                                       "collector summary (redraws in "
                                       "place on a TTY)")
    obt.add_argument("--collector", required=True, metavar="HOST:PORT")
    obt.add_argument("--interval", type=float, default=2.0)
    obt.add_argument("--once", action="store_true",
                     help="print one frame and exit")
    obt.add_argument("--json", action="store_true",
                     help="emit raw summary JSON frames")
    obt.add_argument("--iterations", type=int, default=None,
                     help=argparse.SUPPRESS)
    obl = obsub.add_parser("timeline", help="merged fleet timeline: "
                                            "cross-replica skew table + "
                                            "one chrome trace with a pid "
                                            "lane per process")
    obl.add_argument("--collector", default=None, metavar="HOST:PORT",
                     help="pull the step timeline and known dumps from "
                          "this collector")
    obl.add_argument("--dump", action="append", default=None,
                     metavar="DIR",
                     help="merge this flight-recorder dump directory "
                          "(repeatable)")
    obl.add_argument("--out", default=None,
                     help="write the merged chrome trace JSON here")

    e = sub.add_parser("elastic", help="elastic training membership: "
                                       "status snapshot and manual drain")
    esub = e.add_subparsers(dest="elastic_action", required=True)
    es = esub.add_parser("status", help="epoch, world size and members of "
                                        "a running elastic job")
    es.add_argument("--master", required=True, metavar="HOST:PORT",
                    help="the job's parallel.master endpoint")
    es.add_argument("--timeout", type=float, default=10.0,
                    help="master connect timeout seconds")
    es.add_argument("--json", action="store_true",
                    help="emit the snapshot as JSON")
    ed = esub.add_parser("drain", help="remove a worker from the "
                                       "membership (manual scale-down)")
    ed.add_argument("name", help="worker membership name to remove")
    ed.add_argument("--master", required=True, metavar="HOST:PORT",
                    help="the job's parallel.master endpoint")
    ed.add_argument("--timeout", type=float, default=10.0,
                    help="master connect timeout seconds")

    t = sub.add_parser("train", help="launch a training script with "
                                     "cluster environment")
    t.add_argument("--role", default="trainer",
                   choices=["trainer", "pserver"])
    t.add_argument("--trainers", type=int, default=1)
    t.add_argument("--trainer-id", type=int, default=0)
    t.add_argument("--pservers", default="",
                   help="comma-separated host:port list")
    t.add_argument("--current-endpoint", default="",
                   help="this pserver's host:port")
    t.add_argument("script")
    t.add_argument("script_args", nargs=argparse.REMAINDER)

    args = parser.parse_args(argv)
    try:
        if args.command == "version":
            return _cmd_version(args)
        if args.command == "flags":
            return _cmd_flags(args)
        if args.command == "monitor":
            return _cmd_monitor(args)
        if args.command == "health":
            return _cmd_health(args)
        if args.command == "cache":
            return _cmd_cache(args)
        if args.command == "checkpoint":
            return _cmd_checkpoint(args)
        if args.command == "shard":
            return _cmd_shard(args)
        if args.command == "check":
            return _cmd_check(args)
        if args.command == "analyze":
            return _cmd_analyze(args)
        if args.command == "serve":
            return _cmd_serve(args)
        if args.command == "trace":
            return _cmd_trace(args)
        if args.command == "fleet":
            return _cmd_fleet(args)
        if args.command == "obs":
            return _cmd_obs(args)
        if args.command == "elastic":
            return _cmd_elastic(args)
        if args.command == "train":
            return _cmd_train(args)
    except BrokenPipeError:
        # downstream pager/head closed the pipe; exit quietly
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0
    parser.error(f"unknown command {args.command}")


if __name__ == "__main__":
    sys.exit(main())
