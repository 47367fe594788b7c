#!/usr/bin/env python3
"""Readings for the limits of `correct` (PERF.md section 2), many seeds on one
set-up: the numbers compare.py compares, for

- the program as the configuration states it (the served bfloat16 path), on
  every seed given: the lower readings;
- the control: the reference put in the program's place and computed in float8
  e4m3 (reference.py), on the first `--control-seeds` seeds' own images;
- optionally the program with its own lower-precision path switched on
  (`--program-control-env SPOTTER_TPU_INT8=1,SPOTTER_TPU_INT8_DENSE=1`), on the
  same first seeds: one more server start.

    python3 benchmarks/tools/readings.py --workload r101_bulk --seeds 1,2,3 --seconds 8 \
        --collect chiprun_out/readings/r101_bulk.served.json      # on the chip
    python3 benchmarks/tools/readings.py --workload r101_bulk \
        --judge chiprun_out/readings/r101_bulk.served.json        # on any CPU

(without `--collect`/`--judge` both halves run in one call). The reference and
the controls need no chip: the pool is made again from each seed.

Each seed is a short window at the cell's own load (the same generator, pool,
ladder and clients as a run), long enough to finish the mix's longest
requests; as many replies are compared as a run compares. Prints one JSON line
per reading and writes them to chiprun_out/readings/<workload>.jsonl.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import compare  # noqa: E402
import run as bench  # noqa: E402
import server as srv  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402


def serve_seeds(cell, cfg, mix, checkpoint, seeds, seconds, tag, max_images):
    """One server; a short window per seed. seed -> (served, jpegs, errors, failed)."""
    out = {}
    log_path = os.path.join(bench.WORK_DIR, "logs", f"readings-{tag}.log")
    with srv.Server(cfg, checkpoint, bench.WORK_DIR, log_path) as server:
        ready = server.wait_ready()
        bench.info(f"[{tag}] server ready in {ready:.1f} s")
        for seed in seeds:
            pool = srv.make_images(seed, mix["pool"]["sizes"], int(mix["pool"]["per_size"]))
            with srv.ImageServer(pool) as image_server:
                base = f"http://127.0.0.1:{image_server.port}"
                plan = traffic.Plan(mix, list(pool), base, seed, seconds)
                window = traffic.run_window(plan, server.url)
            failed = sum(1 for r in window.requests if not window.ok(r))
            replies = bench.sample_replies(window, seed, max_images,
                                           cfg["bench"].get("check_per_reply"))
            served, jpegs, errors = bench.parse_replies(replies, pool)
            names = [r.urls[i].rsplit("/", 1)[1].split("?", 1)[0]
                     for r, picks in replies for i in picks]
            rate = sum(len(r.urls) for r in window.done()) / window.window_s
            bench.info(f"[{tag}] seed {seed}: {len(window.requests)} requests, {failed} failed, "
                       f"{rate:.1f} images/s, {len(jpegs)} images sampled")
            out[seed] = {"served": served, "names": names, "errors": errors, "failed": failed,
                         "images_per_s": rate}
        server.stop()
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="", help="comma-separated (collecting)")
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--max-images", type=int, default=0,
                        help="default: the configuration's bench.check_max_images")
    parser.add_argument("--program-control-env", default="")
    parser.add_argument("--collect", metavar="FILE",
                        help="on the chip: serve the seeds, write what was served (no reference)")
    parser.add_argument("--judge", metavar="FILE",
                        help="anywhere (CPU): the reference and the controls over a collected file")
    args = parser.parse_args()
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    cell, cfg, mix = bench.load_cell(manifest, args.workload)
    os.makedirs(bench.WORK_DIR, exist_ok=True)
    checkpoint = weights.ensure_checkpoint(cfg, os.path.join(bench.WORK_DIR, "checkpoints"))
    if args.judge:
        with open(args.judge) as f:
            collected = json.load(f)
    else:
        seeds = [int(s) for s in args.seeds.split(",")]
        max_images = args.max_images or int(cfg["bench"]["check_max_images"])
        collected = {"program": serve_seeds(cell, cfg, mix, checkpoint, seeds, args.seconds,
                                            "program", max_images)}
        if args.program_control_env:
            low = json.loads(json.dumps(cfg))
            for pair in args.program_control_env.split(","):
                key, _, value = pair.partition("=")
                low["serve"]["env"][key] = value
            try:
                collected["program-control"] = serve_seeds(
                    cell, low, mix, checkpoint, seeds[: args.control_seeds], args.seconds,
                    "program-control", max_images)
                collected["program-control-env"] = args.program_control_env
            except srv.BenchFailure as failure:
                bench.info(f"the program's own control gave no number: {failure}")
        if args.collect:
            os.makedirs(os.path.dirname(os.path.abspath(args.collect)), exist_ok=True)
            with open(args.collect, "w") as f:
                json.dump(collected, f)
            return 0

    from reference import Reference, wire

    def jpegs_of(seed, names):
        pool = srv.make_images(int(seed), mix["pool"]["sizes"], int(mix["pool"]["per_size"]))
        return [pool[name][0] for name in names]

    rows, records = [], {}
    ref = Reference(checkpoint)
    for what in ("program", "program-control"):
        for seed, got in collected.get(what, {}).items():
            t0 = time.monotonic()
            recs = ref.images(jpegs_of(seed, got["names"]))
            if what == "program":
                records[seed] = recs
            numbers = compare.compare(list(zip(got["served"], recs)), raw=True)
            rows.append({"what": what, "seed": int(seed), "errors": got["errors"],
                         "failed": got["failed"], "images_per_s": got["images_per_s"],
                         "reference_s": time.monotonic() - t0, **numbers})
    low_ref = Reference(checkpoint, control="fp8")
    for seed in list(collected["program"])[: args.control_seeds]:
        answers = [wire(rec) for rec in
                   low_ref.images(jpegs_of(seed, collected["program"][seed]["names"]))]
        rows.append({"what": "control-fp8", "seed": int(seed),
                     **compare.compare(list(zip(answers, records[seed])), raw=True)})
    out = os.path.join(bench.ROOT, "chiprun_out", "readings")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{args.workload}.jsonl"), "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
            short = {k: v for k, v in row.items() if k not in ("box_gaps", "flips")}
            print(json.dumps(short), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
