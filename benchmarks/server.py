"""The system under test, started as the Quickstart starts it, and the seeded
images it fetches. Copied from chip_smoke.py (PR 21) so that the smoke may
change and the yardstick may not: `Server`, `ImageServer`, `make_images`,
`http_json`. The parent process never imports jax while the child lives: a
chip belongs to one process at a time.
"""

import http.server
import io
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READY_TIMEOUT_S = 1100.0
DRAIN_EXIT_CODE = 83  # lifecycle.PREEMPTED_EXIT_CODE: SIGTERM -> drain -> exit


class BenchFailure(Exception):
    """The run cannot give a result: exit non-zero, print no result line."""


def make_images(seed: int, sizes: list, per_size: int) -> dict:
    """name -> (JPEG bytes, (width, height)). Smooth seeded content (upsampled
    low-frequency noise) so the JPEGs are photo-sized, not noise-sized. Every
    seed gives the same sizes, `per_size` images of each, with other content."""
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(seed)
    jobs = []
    for k in range(per_size):
        for i, (w, h) in enumerate(sizes):
            coarse = (rng.random((12, 16, 3)) * 255).astype(np.uint8)
            jobs.append((f"img{k}_{i}_{w}x{h}.jpg", coarse, (int(w), int(h))))

    def encode(job):
        name, coarse, (w, h) = job
        img = Image.fromarray(coarse).resize((w, h), Image.BICUBIC)
        buf = io.BytesIO()
        img.save(buf, format="JPEG", quality=90)
        return name, (buf.getvalue(), (w, h))

    with ThreadPoolExecutor(max_workers=4) as pool:  # PIL releases the GIL
        return dict(pool.map(encode, jobs))


class ImageServer:
    """Serves the pool on a local port. The query string is ignored, so
    `/<name>?r=<n>` is a distinct URL with the same bytes."""

    def __init__(self, images: dict) -> None:
        payloads = {f"/{name}": data for name, (data, _) in images.items()}

        class Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def do_GET(self):
                body = payloads.get(self.path.split("?", 1)[0])
                if body is None:
                    self.send_error(404)
                    return
                self.send_response(200)
                self.send_header("Content-Type", "image/jpeg")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass

        self._httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, payload=None, timeout: float = 300.0, headers=None):
    """(status, parsed JSON or None). POSTs `payload` as JSON when given."""
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json", **(headers or {})}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as err:
        body = err.read()
        try:
            return err.code, json.loads(body)
        except ValueError:
            return err.code, None


def child_env(cfg: dict, work_dir: str, rehearse: bool, profiler_port: int | None = None) -> dict:
    """The configuration's policy, ladder and env on top of the caller's
    environment. Every cache the child writes lies inside the checkout: the
    compile cache where `JAX_COMPILATION_CACHE_DIR` puts it, else the
    program's own `<checkout>/.jax_cache`; converted params and traces under
    the benchmark's work directory."""
    serve = cfg["serve"]
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["SPOTTER_TPU_DTYPE"] = serve["dtype_policy"]
    env["SPOTTER_TPU_BATCH_BUCKETS"] = ",".join(str(b) for b in serve["batch_buckets"])
    env["SPOTTER_TPU_CACHE"] = os.path.join(work_dir, "param_cache")
    if profiler_port is not None:  # a traced run: collect_trace.py connects to it
        env["SPOTTER_TPU_PROFILER_PORT"] = str(profiler_port)
    env["SPOTTER_TPU_TRACE_DUMP_DIR"] = os.path.join(work_dir, "logs")
    env["HF_HUB_OFFLINE"] = "1"
    env["TRANSFORMERS_OFFLINE"] = "1"
    for key, value in serve.get("env", {}).items():
        env[key] = str(value)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    return env


class Server:
    """One `python -m spotter_tpu.serving.standalone --model <checkpoint>`
    child (warm-up on, integrity verification on), stopped on exit. It is
    started through launch_server.py, which runs that module unchanged and
    reads the device's memory beside it."""

    def __init__(self, cfg: dict, checkpoint: str, work_dir: str, log_path: str,
                 rehearse: bool = False, profiler_port: int | None = None) -> None:
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = log_path
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        self._log = open(log_path, "w")
        self.t0 = time.monotonic()
        self.memory_path = os.path.join(work_dir, "logs", f"memory-{self.port}.json")
        for stale in (self.memory_path, self.memory_path + ".go"):
            if os.path.exists(stale):
                os.remove(stale)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                          "launch_server.py"), self.memory_path,
             "--model", checkpoint, "--host", "127.0.0.1", "--port", str(self.port),
             *cfg["serve"].get("args", [])],
            cwd=ROOT, env=child_env(cfg, work_dir, rehearse, profiler_port),
            stdout=self._log, stderr=subprocess.STDOUT,
        )

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=60)
        self._log.close()

    def log_tail(self, n: int = 40) -> str:
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def wait_ready(self) -> float:
        """Poll /startupz until 200; seconds from spawn to ready."""
        deadline = self.t0 + READY_TIMEOUT_S
        while time.monotonic() < deadline:
            code = self.proc.poll()
            if code is not None:
                raise BenchFailure(f"server exited {code} during bring-up:\n{self.log_tail()}")
            try:
                status, _ = http_json(f"{self.url}/startupz", timeout=5)
            except (urllib.error.URLError, OSError):
                status = None
            if status == 200:
                with open(self.memory_path + ".go", "w"):
                    pass  # the backend is up: the memory reader may start
                return time.monotonic() - self.t0
            time.sleep(0.5)
        raise BenchFailure(f"server not ready after {READY_TIMEOUT_S:.0f} s:\n{self.log_tail()}")

    def metrics(self) -> dict:
        status, body = http_json(f"{self.url}/metrics", timeout=30)
        if status != 200 or not isinstance(body, dict):
            raise BenchFailure(f"/metrics answered {status}")
        return body

    def memory(self) -> dict:
        """device id -> the runtime's own peaks (launch_server.py), read
        within the last quarter of a second; the peaks never fall."""
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            time.sleep(0.3)  # at least one write after whatever ran last
            try:
                with open(self.memory_path) as f:
                    return json.load(f)
            except (OSError, ValueError):
                continue
        raise BenchFailure(f"the memory reader wrote nothing to {self.memory_path}")

    def stop(self) -> None:
        """SIGTERM -> the preemption watcher drains -> the drain exit code."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            code = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise BenchFailure("server ignored SIGTERM for 120 s")
        if code != DRAIN_EXIT_CODE:
            raise BenchFailure(
                f"server exited {code} on SIGTERM, expected {DRAIN_EXIT_CODE}:\n{self.log_tail()}"
            )


class TraceCollector:
    """collect_trace.py as a child: started during set-up, told to `go` inside
    the window, waited for before the window's end is read."""

    def __init__(self, port: int, seconds: float, log_dir: str, host_level: int = 0) -> None:
        self.seconds, self.log_dir = float(seconds), log_dir
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("BENCH_RUN", None)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                          "collect_trace.py"),
             str(port), str(int(round(seconds * 1e3))), log_dir, str(host_level)],
            cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        if self.proc.stdout.readline().strip() != "ready":
            self.close()
            raise BenchFailure("the trace collector did not start")

    def go(self) -> None:
        self.proc.stdin.write("go\n")
        self.proc.stdin.flush()

    def wait(self) -> None:
        try:
            code = self.proc.wait(timeout=self.seconds + 120)
        except subprocess.TimeoutExpired:
            self.close()
            raise BenchFailure("the trace collector did not come back")
        if code != 0:
            raise BenchFailure(f"the trace collector exited {code}")

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        for pipe in (self.proc.stdin, self.proc.stdout):
            pipe.close()
