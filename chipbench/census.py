"""python -m chipbench.census --workload <cell> [<cell> ...] --seeds <n> ...

A SEED CENSUS of a share comparison on the chip (PR 56): the comparison a
cell's kind runs (`against_reference`: the second build's one step and its
inference clone against the float32 reference, the weights of the seed,
the rows the kind would hand it), seed after seed, each in a process of its
own behind JAX's persistent cache. No window is timed: the checks that hold
the timed executable (`timed_steps`, `timed_steps_second_build`) are a
whole run's (`python -m chipbench.run`).

Each seed is read TWICE off one system side: against the reference routed
as the system routed (`routed: true`, what `against_reference` compares
since PR 56) and against the plain reference (`routed: false`, what it
compared before: the first-hand parts are the routed reading's own, they
read the system's own inputs). One JSON line a reading (`seed`, `variant`
"stated", `routed`, `ok`, `failed`, `compared`, `report`), which
`python -m chipbench.limits_study add <file>` files.

    --workers 4   four chips of one host, the seeds dealt round, a process
                  pinned to each. Every seed is a process of its own (a
                  process's memory never falls again); the parent stays
                  off JAX.
    --budget S    a chip begins no seed that would not end inside S
                  seconds of the call: its lines come back
    --plain 0     the routed reading alone
"""

import argparse
import gc
import json
import os
import subprocess
import sys
import time


from chipbench import harness, limits_study

OUT = "chiprun_out/census"


def rows_of(cfg, traffic, kind, seed, compare):
    """(tokens, labels) int32 [rows, S] of the step the kind's comparison
    is made on."""
    rows = int(cfg["reference"]["rows"])
    if compare.__name__.endswith("compare_lm_share"):
        # another seed's stream than the window's chunks
        tok, lab, _ = kind.token_rows(cfg, traffic, seed + 1, rows)
        return tok, lab
    # the window's own chunk 0, as `TokenSource` cuts it from the stream
    n = int(traffic["distinct_chunks"]) * int(traffic["steps_per_chunk"]) \
        * int(cfg["rows_per_step"])
    tok, lab, _ = kind.token_rows(cfg, traffic, seed, n)
    return tok[:rows], lab[:rows]


def readings(compare, fluid, cfg, builder, place, seed, tok, lab,
             plain=True):
    """(routed, report) of one seed, one after the other: the routed
    reading and, with `plain`, the plain one off the same system side."""
    t0 = time.perf_counter()
    got = compare.system_side(fluid, cfg, builder, place, seed, tok, lab)
    gc.collect()
    harness.note(f"census: seed {seed} system side "
                 f"{time.perf_counter() - t0:.1f} s", t0)
    ref = compare.reference_of(cfg, builder, got, tok, lab)
    yield True, compare.judge(cfg, builder, got, ref)
    if plain:
        # the first-hand parts read the system's own inputs: the routed
        # reading's serve
        yield False, compare.judge(cfg, builder, got, dict(
            ref, **compare.reference_of(cfg, builder, got, tok, lab,
                                        routed=False, whole=False)))


def work(cell, seeds, out, plain=True, override=None):
    """One process: the seeds of one cell, a line a reading to `out`."""
    import importlib

    import paddle_tpu as fluid
    from paddle_tpu import amp

    _, _, cfg, traffic, builder, kind = harness.Files().cell(cell)
    if override:
        cfg = dict(cfg, **override.get("config", {}))
        traffic = dict(traffic, **override.get("traffic", {}))
    compare = importlib.import_module(
        "chipbench." + limits_study.MODULES[cfg["name"]])
    place = fluid.TPUPlace(0)
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    if cfg.get("amp"):
        amp.enable(cfg["amp"])
    try:
        with open(out, "a") as log:
            for seed in seeds:
                t0 = time.perf_counter()
                tok, lab = rows_of(cfg, traffic, kind, seed, compare)
                for routed, report in readings(
                        compare, fluid, cfg, builder, place, seed, tok, lab,
                        plain):
                    head = {"seed": seed, "variant": "stated",
                            "routed": routed, "ok": report["ok"],
                            "failed": report["failed"],
                            "seconds": time.perf_counter() - t0}
                    t0 = time.perf_counter()
                    print(json.dumps(dict(head, cell=cell)), flush=True)
                    log.write(json.dumps(dict(
                        head, compared=report["compared"],
                        report=report)) + "\n")
                    log.flush()
                gc.collect()
    finally:
        amp.disable()


def _worker_env(i, n):
    """One chip of the host for process i of n: libtpu's own variables.
    NOT YET RUN ON THE CHIP: PR 56 was handed no four-chip machine (its
    census ran a chip at a time); a process that does not reach its chip
    exits at once, with its reason on standard error."""
    env = dict(os.environ)
    if n > 1:
        env.update(TPU_VISIBLE_DEVICES=str(i), TPU_CHIPS_PER_PROCESS_BOUNDS=
                   "1,1,1", TPU_PROCESS_BOUNDS="1,1,1",
                   TPU_PROCESS_ADDRESSES=f"localhost:{8476 + i}",
                   TPU_PROCESS_PORT=str(8476 + i), CLOUD_TPU_TASK_ID="0")
    return env


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.census")
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--plain", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--tag", default="census")
    ap.add_argument("--timeout", type=float, default=1700.0,
                    help="seconds a seed's process may take")
    ap.add_argument("--budget", type=float, default=None,
                    help="seconds after which a chip begins no seed that "
                    "would not end inside them (by its longest so far)")
    ap.add_argument("--override", help="JSON, as harness.run_cell takes it "
                    "(a tiny size on the CPU)")
    ap.add_argument("--worker", type=int, default=None,
                    help="(internal) this process reads the seeds given")
    args = ap.parse_args(argv)
    if args.worker is not None:
        work(args.workload[0], args.seeds, os.path.join(
            args.out, f"{args.tag}_{args.workload[0]}.jsonl"
            if args.workers <= 1 else
            f"{args.tag}_{args.workload[0]}.w{args.worker}.jsonl"),
            bool(args.plain),
            json.loads(args.override) if args.override else None)
        return 0
    # the parent stays off JAX: a queue of (cell, seed) a chip, EACH SEED A
    # PROCESS OF ITS OWN. Nothing compiled is shared inside a process
    # anyway (each seed builds its programs anew and the reference's jits
    # are made a call: JAX's persistent cache is what serves them), and a
    # process that reads seed after seed grows by the start-up program's
    # constants, 7 GB a seed in the Xing cell (PR 56: ended at 44 GB)
    n, began = args.workers, time.perf_counter()

    def job(i, cell, seed, env):
        cmd = [sys.executable, "-m", "chipbench.census", "--workload", cell,
               "--worker", str(i), "--workers", str(n), "--out", args.out,
               "--tag", args.tag, "--plain", str(args.plain), "--seeds",
               str(seed)]
        if args.override:
            cmd += ["--override", args.override]
        t0 = time.perf_counter()
        try:
            rc = subprocess.run(cmd, env=env, timeout=args.timeout).returncode
        except subprocess.TimeoutExpired:
            rc = "timeout"
        took = time.perf_counter() - t0
        print(f"[census] chip {i} {cell} seed {seed}: rc {rc}, {took:.0f} s",
              file=sys.stderr, flush=True)
        return took

    def chip(i):
        longest, env = 0.0, _worker_env(i, n)
        for cell in args.workload:
            for seed in args.seeds[i::n]:
                if args.budget and time.perf_counter() - began \
                        + 1.2 * longest > args.budget:
                    print(f"[census] chip {i} {cell} seed {seed} left out: "
                          "the budget is spent", file=sys.stderr, flush=True)
                    continue
                longest = max(longest, job(i, cell, seed, env))

    import threading

    threads = [threading.Thread(target=chip, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return 0


if __name__ == "__main__":
    sys.exit(main())
