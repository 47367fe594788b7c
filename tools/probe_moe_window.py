"""Chip probe (hand use): one routed-expert layer alone, `ops/moe.py:
routed_experts` under `jax.jit` at each routed family's published shapes
(4300 tokens an image, hidden 2048, bfloat16; `lfm2`: 32 of 32 experts of 1792
held, 4 a token; `qwen3_next`: 64 of 512 experts of 512 held, 10 a token), and
what a window of it spends its time on.

    chiprun -- python3 tools/probe_moe_window.py 8,32 [path/to/moe.py ...]

Per family and bucket: milliseconds a layer (the host's clock around five
calls, and the program's events in a profiler trace of them), the windows the
routing needed, milliseconds a window, and the window by operation. Every
"XLA Ops" event inside the loop is given its own time (its duration less the
events nested in it) and a label from what its HLO instruction is made of in
the compiled program's text (a fusion: the opcodes fused into it, what its
gathers read, what it writes), so a reading is of the operation itself and not
a subtraction. (The optimised HLO's source lines do not serve: the TPU
compiler gives most of a loop body the line of the loop.) Before the timings,
one image's layer through the bfloat16 kernels against the float32 einsum
form. Further paths are other copies of `moe.py` (a parent's, a step's),
probed the same way beside the module as it stands: parent and change in one
call, on one chip.

Here, with `JAX_PLATFORMS=cpu` and `--rehearse`, it runs tiny shapes through
the same code (no device plane in the trace: labels and counts only).
"""

import importlib.util
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import jax
import jax.numpy as jnp
import numpy as np

import reduce_trace
from spotter_tpu.ops import moe as moe_as_it_stands

TOKENS = 4300
# d, I, experts routed over, held, k, scoring
FAMILIES = {"lfm2": (2048, 1792, 32, 32, 4, "sigmoid"), "qwen3_next": (2048, 512, 512, 64, 10, "softmax")}
TINY = {"lfm2": (256, 128, 8, 8, 2, "sigmoid"), "qwen3_next": (256, 128, 16, 4, 3, "softmax")}
CALLS = 5
OUT = os.path.join("chiprun_out", "probe_moe_window")


def load(path):
    if path is None:
        return moe_as_it_stands
    spec = importlib.util.spec_from_file_location("moe_" + re.sub(r"\W", "_", path), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def inputs(key, m, d, inter, routed, held, k, scoring, moe):
    """On the device: noise tokens, a seeded router with a few crowded
    experts (an image's patches crowd theirs), seeded matrices."""
    kx, kr, kc, kg, kd = jax.random.split(key, 5)
    x = jax.random.normal(kx, (m, d), jnp.float32)
    router = jax.random.normal(kr, (d, routed), jnp.float32) / np.sqrt(d)
    router = router * (1.0 + 2.0 * (jax.random.uniform(kc, (routed,)) > 0.8))
    weights, experts = moe.route(x, router, k, scoring=scoring)
    gate_up = (jax.random.normal(kg, (held, d, 2 * inter), jnp.float32) / np.sqrt(d)).astype(jnp.bfloat16)
    down = (jax.random.normal(kd, (held, inter, d), jnp.float32) / np.sqrt(inter)).astype(jnp.bfloat16)
    return x.astype(jnp.bfloat16), weights, experts, gate_up, down


def made_of(hlo_text):
    """instruction name -> (what it writes, the opcodes it is made of, what
    its gathers read): its own, and for a fusion those of the computation it
    calls. Shapes as the text has them, layout dropped: "bf16[8192,2048]"."""
    shapes, own, calls, inside, current = {}, {}, {}, {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\([^=]*\)|\S+) ([\w\-]+)\(%?([\w.\-]*)", line)
        opened = re.match(r"\s*(?:ENTRY )?%?([\w.\-]+) [^=]*\{\s*$", line)
        if opened and not head:
            current = opened.group(1)
            inside[current] = (set(), set())
            continue
        if not head:
            continue
        name, shape, opcode, operand = head.groups()
        shapes[name] = shape.split("{")[0]
        own[name] = ({opcode}, {operand} if opcode == "gather" else set())
        if current is not None:
            inside[current][0].add(opcode)
            inside[current][1].update(own[name][1])
        calls[name] = re.findall(r"calls=%?([\w.\-]+)", line)
    empty = (set(), set())
    return {name: (shapes[name], opcodes.union(*(inside.get(c, empty)[0] for c in calls[name])),
                   {shapes.get(g, "") for g in gathered.union(*(inside.get(c, empty)[1] for c in calls[name]))})
            for name, (opcodes, gathered) in own.items()}


def _size(shape):
    dims = re.search(r"\[([\d,]*)\]", shape)
    return int(np.prod([int(v) for v in dims.group(1).split(",") if v])) if dims else 0


def label_of(made, rows, d, assignments):
    """What an operation of the layer is for, from what it is made of."""
    out, opcodes, gathered = made
    if "scatter" in opcodes:
        return "scatter-add"
    if any(g.startswith(("bf16[", "f16[")) and "," in g for g in gathered):
        return "gather x[token]"
    if opcodes & {"exponential", "logistic"}:
        return "swiglu"
    if out.startswith("f32[") and _size(out) == rows * d:
        return "weight, mask, layout"
    if "sort" in opcodes:
        return "sort"
    if any(g.startswith("f32[") for g in gathered):
        return "weights' gather"
    if any(_size(g) == assignments for g in gathered):
        return "order[...]"
    return "table gathers" if gathered else "index arithmetic"


def own_times(ops):
    """Each event's duration less the events nested in it, and how many
    `while`s it lies inside (one: the window loop; two: a search nested in it)."""
    ops = sorted(ops, key=lambda e: (e["start_ns"], -e["dur_ns"]))
    stack, rows = [], []
    for ev in ops:
        end = ev["start_ns"] + ev["dur_ns"]
        while stack and stack[-1]["end"] <= ev["start_ns"]:
            stack.pop()
        row = {"name": ev["name"], "own": ev["dur_ns"], "dur": ev["dur_ns"], "end": end,
               "loops": sum(reduce_trace.instruction(s["name"]).startswith("while") for s in stack)}
        if stack:
            stack[-1]["own"] -= ev["dur_ns"]
        stack.append(row)
        rows.append(row)
    return rows


def against_float32(moe, shapes, tokens, interpret):
    """One image's layer through the kernels in bfloat16 against the module as
    it stands in float32 through the einsum form: largest and mean gap, scale."""
    d, inter, routed, held, k, scoring = shapes
    x, weights, experts, gate_up, down = inputs(jax.random.PRNGKey(1), tokens, *shapes, moe)
    kwargs = {"tile": 8, "window_rows": 64, "interpret": True} if interpret else {}
    got = jax.jit(lambda *a: moe.routed_experts(*a, impl="pallas", **kwargs))(x, weights, experts, gate_up, down)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: moe_as_it_stands.routed_experts(*a, impl="einsum", tile=8 if interpret else 128,
                                                               window_rows=64 if interpret else 2048))(
            *(a.astype(jnp.float32) for a in (x, weights)), experts, *(a.astype(jnp.float32) for a in (gate_up, down)))
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return {"max_gap": float(np.abs(got - want).max()), "mean_gap": float(np.abs(got - want).mean()),
            "scale": float(np.abs(want).mean()), "finite": bool(np.isfinite(got).all())}


def probe(moe, family, shapes, b, tokens, interpret):
    d, inter, routed, held, k, scoring = shapes
    m = tokens * b
    args = jax.jit(lambda key: inputs(key, m, d, inter, routed, held, k, scoring, moe))(jax.random.PRNGKey(b))
    counts = np.asarray(moe.held_tokens(args[2].reshape(1, -1), 0, held))[0]
    tile = moe.ROW_TILE if not interpret else 8
    padded = int((-(-counts // tile) * tile).sum())
    rows_w = moe.WINDOW_ROWS if not interpret else 64
    windows = -(-padded // rows_w)
    kwargs = {"tile": tile, "window_rows": rows_w, "impl": "pallas", "interpret": True} if interpret else {}
    fn = jax.jit(lambda *a: moe.routed_experts(*a, **kwargs))
    t0 = time.time()
    compiled = fn.lower(*args).compile()
    compile_s = time.time() - t0
    jax.block_until_ready(compiled(*args))
    trace_dir = os.path.join(OUT, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    t0 = time.time()
    for _ in range(CALLS):
        out = compiled(*args)
    jax.block_until_ready(out)
    wall_ms = 1e3 * (time.time() - t0) / CALLS
    jax.profiler.stop_trace()
    events, _ = reduce_trace.load_xplane(reduce_trace.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    result = {"family": family, "bucket": b, "held_rows": int(counts.sum()), "padded_rows": padded,
              "fullest_over_mean": float(counts.max() / max(counts.mean(), 1)), "windows": windows,
              "compile_s": compile_s, "wall_ms_a_layer": wall_ms}
    ops = [e for e in events if e["plane"].startswith(reduce_trace.DEVICE_PREFIX)
           and e["line"] == reduce_trace.OPS_LINE]
    programs = [e for e in events if e["plane"].startswith(reduce_trace.DEVICE_PREFIX)
                and e["line"] == reduce_trace.MODULES_LINE]
    hlo = compiled.as_text()
    made = made_of(hlo)
    if not ops:  # no device plane: a rehearsal
        result["labels_in_the_program"] = sorted({label_of(v, rows_w, d, m * k) for v in made.values()})
        return result, hlo
    result["device_ms_a_layer"] = sum(e["dur_ns"] for e in programs) / 1e6 / CALLS
    by_label, by_op = {}, {}
    for row in own_times(ops):
        name = reduce_trace.instruction(row["name"])
        where = "window" if row["loops"] or name.startswith("while") else "layer"
        if name.startswith("while"):
            label = "search" if row["loops"] else "loop control"
        elif row["loops"] > 1:
            label = "search"
        elif "expert_matmul_kernel" in row["name"].split(" = ")[0]:
            # the two calls apart by what they write: rows x d float32, or the down product's operand
            written = _size(row["name"].split(" = ", 1)[-1].split("{")[0])
            label = "kernel down" if written == rows_w * d else "kernel gate_up"
        else:
            label = label_of(made.get(name, ("", set(), set())), rows_w, d, m * k)
        key = (where, label)
        by_label[key] = by_label.get(key, 0.0) + row["own"]
        op = by_op.setdefault((where, label, row["name"][:200]), [0, 0.0])
        op[0] += 1
        op[1] += row["own"]
    per = {"window": 1e6 * CALLS * max(windows, 1), "layer": 1e6 * CALLS}
    result["ms_a_window"] = sum(ns for (where, _), ns in by_label.items() if where == "window") / per["window"]
    result["window_by_operation_ms"] = {
        label: ns / per["window"] for (where, label), ns in sorted(by_label.items(), key=lambda kv: -kv[1])
        if where == "window"}
    result["outside_the_loop_ms_a_layer"] = {
        label: ns / per["layer"] for (where, label), ns in sorted(by_label.items(), key=lambda kv: -kv[1])
        if where == "layer"}
    result["ops"] = [{"where": where, "label": label, "name": name, "calls": calls, "own_ms": ns / 1e6}
                     for (where, label, name), (calls, ns) in sorted(by_op.items(), key=lambda kv: -kv[1][1])]
    return result, hlo


def main():
    argv = [a for a in sys.argv[1:] if a != "--rehearse"]
    rehearse = len(argv) != len(sys.argv) - 1
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if dev.platform != "tpu" and not rehearse:
        sys.exit("no TPU: a time comes only from the chip (--rehearse runs tiny shapes here)")
    buckets = [int(a) for a in argv[0].split(",")]
    paths = [None] + argv[1:]
    tokens = 40 if rehearse else TOKENS
    os.makedirs(OUT, exist_ok=True)
    results = []
    for family, shapes in (TINY if rehearse else FAMILIES).items():
        for path in paths:
            print(family, path or "as it stands", json.dumps(against_float32(load(path), shapes, tokens, rehearse)),
                  flush=True)
        for b in buckets:
            for path in paths:
                moe = load(path)
                tag = os.path.splitext(os.path.basename(path))[0] if path else "as_it_stands"
                result, hlo = probe(moe, family, shapes, b, tokens, rehearse)
                result["module"] = tag
                results.append(result)
                with open(os.path.join(OUT, f"{tag}_{family}_{b}.hlo.txt"), "w") as f:
                    f.write(hlo)
                print(json.dumps({k: v for k, v in result.items() if k != "ops"}), flush=True)
                for op in result.get("ops", [])[:12]:
                    print(f"    {op['own_ms'] / CALLS:9.3f} ms a layer x{op['calls'] // CALLS:<4d} "
                          f"{op['where']:6s} {op['label']:28s} {op['name'][:110]}", flush=True)
                with open(os.path.join(OUT, f"results_{argv[0]}.json"), "w") as f:
                    json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
