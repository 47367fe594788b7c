#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of BENCHMARK.json is one configuration (benchmarks/configs/<name>.json)
under one traffic mix (benchmarks/traffic/<mix>.json). A run:

1. set-up (all of it counted as `setup_s`): the checkpoint of the
   configuration's seeded weights (weights.py; written once per checkout),
   the seeded JPEG pool on a local HTTP port, then the server as the
   Quickstart starts it: `python -m spotter_tpu.serving.standalone --model
   <checkpoint>` with the configuration's policy, ladder, flags and env,
   warm-up on, integrity verification on; wait on /startupz; a few warm
   requests;
2. the window: `POST /detect` over HTTP, sent for `--seconds` as the mix says
   and every reply waited for; `/metrics` before and after; with `--trace 1`
   one capture of the device inside it (collect_trace.py);
3. SIGTERM, wait for the child;
4. `correct`: a seeded sample of the window's own replies, the longest among
   them, against the plain reference (reference.py, compare.py);
5. one JSON object as the last line of stdout.

With `--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics; each metric is a reader of its own,
benchmarks/metrics/<name>.py, found by name. A run that finds no TPU, or
fewer chips than the cell asks for, exits non-zero and prints no result.
`--rehearse` is the CPU rehearsal (the tiny configuration the cell's
configuration names, JAX_PLATFORMS=cpu): it prints a line marked as a
rehearsal, under no metric's name, and says nothing about the chip.
"""

import argparse
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import compare  # noqa: E402
import server as srv  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

WORK_DIR = os.path.join(ROOT, ".bench_work")  # inside the checkout, at a fixed path
TRACE_SECONDS = 8.0  # some twenty programs at the cells' rates
TRACE_DELAY_S = 3.0
HOST_STAGES = ("fetch", "decode", "h2d", "postprocess")


def info(message: str) -> None:
    print(f"[bench] {message}", file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_reader(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module, found by the manifest's name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise srv.BenchFailure(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(manifest: dict, name: str) -> tuple[dict, dict, dict]:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise srv.BenchFailure(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    mix = traffic.load_mix(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    return cell, cfg, mix


def metrics_of(manifest: dict, cell: dict, group: str) -> list:
    return [m for m in manifest[group] if cell["name"] in m.get("workloads", [cell["name"]])]


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise srv.BenchFailure(f"device kind {kind!r} is not in benchmarks/peaks.json")
    return table[kind]


def sample_replies(window, seed: int, max_images: int, per_reply: int | None = None) -> list:
    """What is checked: [(kept reply, the positions of its images that are
    compared)]. Of each stream the longest reply that finished, then others
    drawn from the seed, up to `max_images` images in all (the configuration's
    `bench.check_max_images`: the reference's time an image differs a
    hundredfold between families). With `per_reply` (`bench.check_per_reply`)
    a reply gives that many of its images, drawn from the seed, so that a
    costly reference still sees several replies from all through the window;
    without it a reply gives all of them."""
    import numpy as np

    kept = [r for r in window.requests if r.body is not None and r.status == 200]
    rng = np.random.default_rng([int(seed), 11])
    by_stream: dict[str, list] = {}
    for r in kept:
        by_stream.setdefault(r.stream, []).append(r)
    first = [max(by_stream[stream], key=lambda r: (len(r.urls), -r.ordinal, -r.sender))
             for stream in sorted(by_stream)]
    rest = [r for r in kept if r not in first]
    chosen, images = [], 0
    for r in first + [rest[int(i)] for i in rng.permutation(len(rest))]:
        n = len(r.urls) if per_reply is None else min(per_reply, len(r.urls))
        if r not in first and images + n > max_images:
            continue
        picks = sorted(int(i) for i in rng.choice(len(r.urls), size=n, replace=False))
        chosen.append((r, picks))
        images += n
    return chosen


def parse_replies(replies: list, pool: dict) -> tuple[list, list, int]:
    """(served detections per image, the images' JPEG bytes, images that came
    back as errors) of the sampled replies' picked images."""
    served, jpegs, errors = [], [], 0
    for r, picks in replies:
        body = json.loads(r.body)
        if [i["url"] for i in body["images"]] != r.urls:
            raise srv.BenchFailure("a reply's urls are not the request's, in order")
        errors += sum(1 for item in body["images"] if "error" in item)
        for item in (body["images"][i] for i in picks):
            if "error" in item:
                continue
            name = item["url"].rsplit("/", 1)[1].split("?", 1)[0]
            jpegs.append(pool[name][0])
            served.append([(d["label"], d["box"]) for d in item["detections"]])
    return served, jpegs, errors


def check_replies(replies: list, pool: dict, checkpoint: str) -> tuple[dict, int]:
    """(numbers compared, images that came back as errors)."""
    from reference import Reference

    served, jpegs, errors = parse_replies(replies, pool)
    ref = Reference(checkpoint)
    t0 = time.monotonic()
    records = ref.images(jpegs)
    info(f"reference: {len(jpegs)} images in {time.monotonic() - t0:.1f} s")
    return compare.compare(list(zip(served, records))), errors


def name_idle_gaps(trace: dict, capture_metrics: list) -> None:
    """The capture traces no host, so an idle gap lies under no host event. It
    is named by the host stage of `/metrics` whose summed time grew most
    between the reads that bracket the capture (waiting in the queue is no
    work and is left out)."""
    before, after = (m["stage_ms_histogram"] for _, m in capture_metrics)
    grew = {s: after[s]["sum"] - before.get(s, {"sum": 0.0})["sum"]
            for s in HOST_STAGES if s in after}
    if not grew:
        return
    stage = max(grew, key=grew.get)
    label = f"host not traced; stage that grew most: {stage} ({grew[stage] / 1e3:.2f} s summed)"
    for gap in trace["idle_gaps"]:
        if gap[0] == "host: nothing traced":
            gap[0] = label


def main(argv=None, keep_trace_to: str | None = None) -> int:
    """`keep_trace_to`: a directory the raw trace and `/metrics` are copied to
    (tools/preflight.py, by hand); the benchmark's own command never sets it."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU rehearsal at tiny size; says nothing about the chip")
    args = parser.parse_args(argv)
    try:
        return run(args, keep_trace_to)
    except srv.BenchFailure as failure:
        print(f"[bench] FAILED: {failure}", file=sys.stderr, flush=True)
        log_path = os.path.join(WORK_DIR, "logs", f"server-{args.workload}.log")
        if os.path.exists(log_path):
            with open(log_path, errors="replace") as f:
                tail = [ln for ln in f.readlines() if "aiohttp.access" not in ln][-25:]
            print("[bench] the server's last lines:\n" + "".join(tail), file=sys.stderr, flush=True)
        return 1


def run(args, keep_trace_to: str | None = None) -> int:
    manifest_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isdir(os.path.join(ROOT, "spotter_tpu")):
        raise srv.BenchFailure(f"no spotter_tpu package beside {HERE}: nothing to measure")
    manifest = load_json(manifest_path)
    cell, cfg, mix = load_cell(manifest, args.workload)
    named = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if args.rehearse:
        cfg = load_json(os.path.join(HERE, "configs", f"{cfg['serve']['rehearse_config']}.json"))
    elif named == "cpu":
        raise srv.BenchFailure("JAX_PLATFORMS=cpu names no accelerator; this run needs a TPU")

    # ---- set-up ---------------------------------------------------------
    t_setup = time.monotonic()
    os.makedirs(WORK_DIR, exist_ok=True)
    checkpoint = weights.ensure_checkpoint(cfg, os.path.join(WORK_DIR, "checkpoints"))
    t_ckpt = time.monotonic() - t_setup
    pool = srv.make_images(args.seed, mix["pool"]["sizes"], int(mix["pool"]["per_size"]))
    t_pool = time.monotonic() - t_setup - t_ckpt
    log_path = os.path.join(WORK_DIR, "logs", f"server-{args.workload}.log")
    trace_dir = os.path.join(WORK_DIR, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    profiler_port = srv.free_port() if args.trace else None
    collector, capture_metrics = None, []
    with srv.ImageServer(pool) as image_server, \
            srv.Server(cfg, checkpoint, WORK_DIR, log_path, args.rehearse, profiler_port) as server:
        ready_s = server.wait_ready()
        status, health = srv.http_json(f"{server.url}/healthz")
        device = (health or {}).get("device") or {}
        want = "cpu" if args.rehearse else "tpu"
        if status != 200 or device.get("platform") != want:
            raise srv.BenchFailure(f"/healthz {status}: server runs on {device}, not on a {want}")
        if not args.rehearse and device.get("count") != cell["chips"]:
            raise srv.BenchFailure(f"the cell asks for {cell['chips']} chip(s), JAX sees {device}")
        base = f"http://127.0.0.1:{image_server.port}"
        plan = traffic.Plan(mix, list(pool), base, args.seed, args.seconds)
        # warm requests: the decode pool, the connection paths and both ends
        # of the mix's request sizes, before the window and counted as set-up
        for stream in mix["streams"]:
            for n in sorted(set(stream["images"])):
                urls = [f"{base}/{name}?warm={i}" for i, name in enumerate(list(pool)[:n])]
                code, _ = srv.http_json(f"{server.url}/detect", {"image_urls": urls})
                if code != 200:
                    raise srv.BenchFailure(f"warm request of {n} images answered {code}")
        if args.trace:
            capture_s = min(TRACE_SECONDS, max(0.5, args.seconds - TRACE_DELAY_S - 1.0))
            collector = srv.TraceCollector(profiler_port, capture_s, trace_dir,
                                           host_level=1 if args.rehearse else 0)
        m0 = server.metrics()
        ladder = cfg["serve"]["batch_buckets"]
        if m0["compiles_total"] != len(ladder):
            raise srv.BenchFailure(
                f"{m0['compiles_total']} programs compiled for a ladder of {ladder}: "
                f"{m0.get('compile_shapes')}")
        setup_s = time.monotonic() - t_setup
        info(f"set-up {setup_s:.1f} s (checkpoint {t_ckpt:.1f}, pool {t_pool:.1f}, "
             f"spawn to ready {ready_s:.1f}; the server's own time_to_ready_s "
             f"{m0.get('time_to_ready_s')}); compile seconds per bucket: "
             + ", ".join(f"{e['shape']}={e['wall_s']:.1f}" for e in m0["compile_shapes"]))

        # ---- the window ---------------------------------------------------
        tracer = None
        if collector:
            def capture():
                time.sleep(TRACE_DELAY_S)
                capture_metrics.append((time.monotonic(), server.metrics()))
                t_go = time.monotonic()
                collector.go()
                time.sleep(collector.seconds)  # the second read brackets the capture, not its way back
                capture_metrics.append((time.monotonic(), server.metrics()))
                collector.wait()
                info(f"the capture of {collector.seconds:.1f} s came back after "
                     f"{time.monotonic() - t_go:.1f} s")

            tracer = threading.Thread(target=capture)
        try:
            window = traffic.run_window(
                plan, server.url, on_started=(lambda _t0: tracer.start()) if tracer else None)
            if tracer:
                tracer.join()
        finally:
            if collector:
                collector.close()
        m1 = server.metrics()
        compiled_in_window = m1["compiles_total"] - m0["compiles_total"]
        if compiled_in_window:
            raise srv.BenchFailure(f"{compiled_in_window} program(s) compiled inside the window")
        # what a chip holds at its peak: the buffers in use and what the
        # compiled programs reserve for their temporaries (launch_server.py)
        memory = server.memory()
        memory_peak = max(row["peak_bytes_in_use"] + row["peak_bytes_reserved"]
                          for row in memory.values())
        info("memory, per device: " + "; ".join(
            f"{dev}: in use at peak {row['peak_bytes_in_use'] / 2**30:.3f} GiB + reserved by "
            f"programs {row['peak_bytes_reserved'] / 2**30:.3f} GiB" for dev, row in memory.items())
            + f"; /metrics hbm_per_device {m1.get('hbm_per_device')}")
        server.stop()

    attempted = len(window.requests)
    failed = attempted - len(window.done())
    by_close = sum(len(r.urls) for r in window.done() if r.done_at <= window.t_close)
    info(f"window {window.window_s:.2f} s (sending closed at {window.t_close - window.t0:.2f} s): "
         f"{attempted} requests sent, {failed} failed; images answered by the close "
         f"{by_close}, by the last reply {sum(len(r.urls) for r in window.done())}")
    sixths = [0] * 7  # how steady the rate is inside a run; the last holds what came after the close
    for r in window.done():
        sixths[min(6, int(6 * (r.done_at - window.t0) / (window.t_close - window.t0)))] += len(r.urls)
    info(f"images answered in each sixth of the sending time {sixths[:6]}, after it {sixths[6]}")
    for r in window.requests:
        if not window.ok(r):
            text = r.error or (r.body or b"").decode("utf-8", "replace")
            at = max(0, text.find('"error"'))
            info(f"first failed request: status {r.status}: {text[max(0, at - 100):at + 300]}")
            break

    # ---- the trace --------------------------------------------------------
    trace = None
    if args.trace:
        if len(capture_metrics) != 2:
            raise srv.BenchFailure("the capture did not run to its end")
        import reduce_trace

        path = reduce_trace.find_xplane(trace_dir)
        if path is None:
            raise srv.BenchFailure(f"no .xplane.pb under {trace_dir}")
        t0 = time.monotonic()
        events, capture_ns = reduce_trace.load_xplane(path)
        trace = reduce_trace.reduce(events, capture_ns=capture_ns, capture_s=collector.seconds)
        info(f"trace {os.path.getsize(path) / 2**20:.1f} MiB reduced in "
             f"{time.monotonic() - t0:.1f} s")
        if keep_trace_to:
            os.makedirs(keep_trace_to, exist_ok=True)
            shutil.copy(path, os.path.join(keep_trace_to, f"{args.workload}-{args.seed}.xplane.pb"))
            kept = os.path.join(keep_trace_to, f"{args.workload}-{args.seed}.metrics.json")
            with open(kept, "w") as f:
                json.dump({"before": m0, "after": m1, "capture": capture_metrics}, f)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if not args.rehearse and not trace.get("devices"):
            raise srv.BenchFailure("the trace holds no device plane: nothing ran on a chip")
        if trace.get("devices"):
            name_idle_gaps(trace, capture_metrics)
            info(f"traced {trace['window_s']:.2f} s (anchored to the capture: "
                 f"{trace['window_anchored']}), busy {trace['busy_s']:.2f} s, programs "
                 + ", ".join(f"{n}: {r['runs']:.0f} runs, {r['seconds']:.3f} s"
                             for n, r in trace["programs"].items()))

    # ---- metrics ----------------------------------------------------------
    kind = device.get("device_kind") or m1.get("device_kind")
    ctx = {
        "cell": cell, "config": cfg, "mix": mix, "window": window, "seconds": args.seconds,
        "metrics_before": m0, "metrics_after": m1, "setup_s": setup_s, "trace": trace,
        "capture_metrics": capture_metrics,
        "peaks": None if args.rehearse else peaks_for(kind),
        "kernel": lambda name: load_reader("kernels", name),
    }
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for metric in metrics_of(manifest, cell, group):
        value = load_reader("metrics", metric["name"]).read(ctx)
        if value is not None:
            metrics[metric["name"]] = {"value": float(value), "unit": metric["unit"]}

    # ---- correct ----------------------------------------------------------
    replies = sample_replies(window, args.seed, int(cfg["bench"]["check_max_images"]),
                             cfg["bench"].get("check_per_reply"))
    numbers, errors = check_replies(replies, pool, checkpoint)
    correct, table = compare.judge(numbers, compare.load_limits(cfg["name"]), errors)
    table["failed_requests"] = [failed, 0]
    correct = correct and failed == 0
    for name, (number, limit) in table.items():
        info(f"compared {name}: {number} (limit {limit if limit is not None else 'none: shown, not judged'})")

    result = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": device.get("platform"), "kind": kind,
            "count": device.get("count"), "memory_peak_bytes": memory_peak,
        },
    }
    if trace is not None and trace.get("devices"):
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    result["compared"] = table
    if args.rehearse:
        # a rehearsal's numbers stand under no metric's name
        result = {"rehearsal": True, "correct": correct, "attempted": attempted,
                  "failed": failed, "readers_ran": sorted(metrics), "compared": table}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
