"""TPU inference engine: static-shape buckets, padded batching, jit cache.

This is the device half of what `serve.py:98-109` does per image in the
reference — but batched and shape-disciplined for XLA:

- batch sizes come from a fixed ladder (pad up to the next bucket), so the
  number of compiled programs is bounded (SURVEY.md §5.7);
- preprocess produces one static (H, W) per model family;
- postprocess returns fixed-k tensors on device; thresholding happens on host.

The engine is synchronous (one device stream); `MicroBatcher` feeds it from
async request handlers.
"""

import itertools
import logging
import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from PIL import Image

from spotter_tpu import obs
from spotter_tpu.engine.errors import (
    FatalEngineError,
    TransientEngineError,
    as_typed,
    classify_engine_exception,
)
from spotter_tpu.engine.metrics import Metrics
from spotter_tpu.engine.staging import Slab, StagingSlabs
from spotter_tpu.testing import faults
from spotter_tpu.ops.postprocess import (
    sigmoid_max_postprocess,
    sigmoid_topk_postprocess,
    softmax_postprocess,
    to_detections,
)
from spotter_tpu.obs import perf as perf_mod
from spotter_tpu.obs.perf import sample_hbm_once
from spotter_tpu.ops.preprocess import (
    DecodePool,
    PreprocessSpec,
    decode_resize_uint8,
    device_preprocess_supported,
    device_rescale_normalize,
    preprocess_image,
    shortest_edge_size,
)

logger = logging.getLogger(__name__)

DEVICE_PREPROCESS_ENV = "SPOTTER_TPU_DEVICE_PREPROCESS"

# How long a detect() call will wait for an in-progress degraded rebuild
# (compile of the rescaled ladder included) before proceeding anyway; the
# batcher watchdog bounds the overall call regardless.
REBUILD_GATE_WAIT_S = 300.0

# Per-image counters a module may return beside "logits" and "pred_boxes";
# the engine fetches them with the detections and adds them to /metrics.
PROGRAM_COUNTERS = ("moe_expert_tokens", "moe_assignments", "moe_bias_moved", "kda_gate_spread")

POSTPROCESS_KINDS = {
    "sigmoid_topk": sigmoid_topk_postprocess,      # RT-DETR family
    "softmax": softmax_postprocess,                # DETR / YOLOS
    "sigmoid_max": sigmoid_max_postprocess,        # OWL-ViT
}


@dataclass
class BuiltDetector:
    """Everything the engine needs for one loaded model (registry output)."""

    model_name: str
    module: object  # flax module with .apply
    params: dict
    preprocess_spec: PreprocessSpec
    postprocess: str  # key into POSTPROCESS_KINDS
    id2label: dict[int, str]
    num_top_queries: int = 300
    # extra static kwargs passed to module.apply (e.g. OWL-ViT text inputs)
    apply_kwargs: dict = field(default_factory=dict)
    # DETR-style models consume the preprocess pixel mask (padded buckets)
    needs_mask: bool = False
    # Open-vocabulary runtime path (ISSUE 13): list[str] queries ->
    # normalized (Q, proj) float32 embeddings through the model's text
    # tower. None = closed-set family; the engine then rejects qset detects.
    text_encoder: Optional[Callable] = None


@dataclass
class _Batch:
    """One batch on its way through the engine: stage -> upload -> dispatch
    -> fetch. Its sequence number names it on every span it causes (the
    request traces, the span table's annotations in a profiler capture),
    and `stages` fills with each stage's seconds as that stage's span
    closes."""

    seq: int
    n: int
    bucket: int
    qset: object = None
    arrays: tuple = ()  # host arrays (views of `slab`), then their device copies
    slab: Optional[Slab] = None  # leased until the outputs are on the host
    outputs: tuple = ()
    meta: Optional[dict] = None
    stages: dict = field(default_factory=dict)
    total: Optional[obs.span] = None  # engine.batch: staging start -> answers
    device: Optional[obs.span] = None  # the device stage: dispatch -> on host

    @property
    def tags(self) -> dict:
        return {"batch": self.seq, "bucket": self.bucket, "n": self.n}


def _bitpattern_u32(x):
    """Reinterpret an array's raw bits as uint32 words (2-byte dtypes
    widen; integer/bool dtypes cast with wraparound). Bit-identical on
    device and host so attestation sums can be compared exactly."""
    dt = jnp.dtype(x.dtype)
    if dt == jnp.float32:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if dt.itemsize == 2:
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    return x.astype(jnp.uint32)


_ATTEST_JIT = None


def _attest_sum(x) -> int:
    """jit'd on-device checksum: sum of the bitpattern words mod 2^32.
    Integer addition is order-independent, so the result is identical
    under any sharding/reduction order — a float reduction would not be.
    jit'd once (cached per shape/dtype); computation runs on whatever
    device the committed input lives on, only the scalar comes back."""
    global _ATTEST_JIT
    if _ATTEST_JIT is None:
        _ATTEST_JIT = jax.jit(lambda a: jnp.sum(_bitpattern_u32(a)))
    return int(_ATTEST_JIT(x))


def _host_checksum(a: np.ndarray) -> int:
    """Host-side mirror of `_attest_sum` over the trusted checkpoint copy
    (numpy, no device involved): same bitpattern words, same mod-2^32 sum."""
    a = np.ascontiguousarray(a)
    if a.dtype == np.float32:
        u = a.view(np.uint32)
    elif a.dtype.itemsize == 2:
        u = a.view(np.uint16)
    else:
        u = a
    return int(u.sum(dtype=np.uint64) % (2**32))  # no widened copy: a leaf may hold 1e8 words


def describe_devices(devices: Sequence) -> dict:
    """The `device` block of /healthz: what JAX reports for the devices an
    engine places work on. Raises when they are CPUs nobody asked for: a
    replica that found no accelerator must fail bring-up, not serve from
    the host without a word. CPU serving (tests, the stub fleet) is asked
    for by name, with `JAX_PLATFORMS` naming `cpu`."""
    info = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }
    named = (jax.config.jax_platforms or "").lower().split(",")
    if info["platform"] == "cpu" and "cpu" not in named:
        raise RuntimeError(
            f"no accelerator: JAX placed the engine on {info} and "
            f"JAX_PLATFORMS={jax.config.jax_platforms!r} does not name cpu — "
            "refusing to serve from the host silently (set JAX_PLATFORMS=cpu "
            "to ask for it)"
        )
    return info


def default_batch_buckets(max_batch: int = 8) -> tuple[int, ...]:
    sizes = []
    b = 1
    while b <= max_batch:
        sizes.append(b)
        b *= 2
    return tuple(sizes)


class InferenceEngine:
    """Owns device params + compiled programs; turns PIL images into detections."""

    def __init__(
        self,
        built: BuiltDetector,
        threshold: float = 0.5,
        batch_buckets: Sequence[int] = (1, 2, 4, 8),
        device: Optional[jax.Device] = None,
        metrics: Optional[Metrics] = None,
        donate_pixels: bool = True,
        mesh=None,
        tp_rules: Sequence = (),
        device_preprocess: Optional[bool] = None,
        decode_pool: Optional[DecodePool] = None,
    ) -> None:
        """`mesh`: optional ("dp","tp") Mesh — batch axis sharded over "dp",
        params replicated (or TP-split per `tp_rules`); XLA inserts the
        collectives. Without a mesh, single-device placement as before.

        `device_preprocess` (default: SPOTTER_TPU_DEVICE_PREPROCESS env):
        host ships uint8 NHWC (3 B/px of H2D instead of the float path's
        16 B/px pixels+mask) and rescale/normalize/mask run inside the
        forward jit (ops/preprocess.py: device_rescale_normalize). Falls
        back to the host float path for specs it can't express (pad_square).
        `decode_pool` parallelizes the remaining host decode/resize work
        (SPOTTER_TPU_DECODE_WORKERS); shared across engines when passed in.
        """
        self.built = built
        self.threshold = threshold
        self.metrics = metrics or Metrics()
        self.tp_rules = tuple(tp_rules)
        if device_preprocess is None:
            device_preprocess = (
                os.environ.get(DEVICE_PREPROCESS_ENV, "0").strip() not in ("", "0")
            )
        self.device_preprocess = bool(device_preprocess) and device_preprocess_supported(
            built.preprocess_spec
        )
        self._decode_pool = decode_pool or DecodePool()
        self._place(mesh, device, batch_buckets)
        # Fault-domain state (ISSUE 4): the dp width this engine was built
        # for, a generation counter bumped by every in-place rebuild, and a
        # gate detect() waits on while a degraded rebuild swaps placement.
        self.initial_dp = self.dp
        self.generation = 0
        self._rebuild_gate = threading.Event()
        self._rebuild_gate.set()
        # Double-buffered H2D (ISSUE 9): stage-put + dispatch are serialized
        # by this lock so concurrent batcher worker threads never interleave
        # their shard uploads, while _finish (the blocking jax.device_get)
        # runs OUTSIDE it — batch N+1's async _put overlaps batch N's device
        # step and D2H fetch instead of queueing behind them. The host
        # decode half stays outside the lock too, so decode keeps its
        # thread-level parallelism.
        self._h2d_lock = threading.Lock()
        self._batch_seq = itertools.count(1)
        # Compile-provenance thread-local (ISSUE 10): warmup / traffic /
        # oom_downgrade / rebuild tag every compile-ledger entry with WHY
        # the program compiled.
        self._compile_src = threading.local()
        self._counter_names: tuple = ()  # the counters this program returns, set at its trace
        post_fn = POSTPROCESS_KINDS[built.postprocess]
        k = built.num_top_queries

        def apply_post(params, pixels, masks, target_sizes):
            args = (pixels, masks) if built.needs_mask else (pixels,)
            out = built.module.apply({"params": params}, *args, **built.apply_kwargs)
            # what a program counts of itself (a routed-expert layer's
            # tokens per expert) rides back behind the detections, per image
            self._counter_names = tuple(name for name in PROGRAM_COUNTERS if name in out)
            counters = tuple(out[name] for name in self._counter_names)
            # named scopes are op metadata only: they put the program's
            # sections on a device trace's op names, and change no program
            with jax.named_scope("postprocess"):
                if built.postprocess == "sigmoid_topk":
                    kk = min(k, out["logits"].shape[1] * out["logits"].shape[2])
                    return sigmoid_topk_postprocess(
                        out["logits"], out["pred_boxes"], target_sizes, k=kk
                    ) + counters
                return post_fn(out["logits"], out["pred_boxes"], target_sizes) + counters

        if self.device_preprocess:
            spec = built.preprocess_spec

            # uint8 in, rescale/normalize/mask fused into the forward
            # program — the float pixel tensor only ever exists in HBM
            def forward(params, pixels_u8, valid_hw, target_sizes):
                with jax.named_scope("preprocess"):
                    pixels, masks = device_rescale_normalize(
                        pixels_u8, valid_hw, spec
                    )
                return apply_post(params, pixels, masks, target_sizes)

        else:
            forward = apply_post

        self._forward_fn = forward

        # Open-vocabulary forward (ISSUE 13): same staging substrate, but the
        # query matrix is an ARGUMENT instead of a baked jit constant, so one
        # engine serves arbitrary vocabularies. The query count is padded to
        # a bucket (caching/text_cache.py QUERY_PAD) with a validity mask, so
        # the compile count is bounded by pad multiples, not vocabularies.
        self._forward_q_fn = None
        if built.text_encoder is not None:

            def apply_post_q(params, pixels, masks, target_sizes,
                             query_embeds, query_mask):
                args = (pixels, masks) if built.needs_mask else (pixels,)
                out = built.module.apply(
                    {"params": params}, *args,
                    query_embeds=query_embeds, query_mask=query_mask,
                )
                with jax.named_scope("postprocess"):
                    return sigmoid_max_postprocess(
                        out["logits"], out["pred_boxes"], target_sizes
                    )

            if self.device_preprocess:
                spec_q = built.preprocess_spec

                def forward_q(params, pixels_u8, valid_hw, target_sizes,
                              query_embeds, query_mask):
                    with jax.named_scope("preprocess"):
                        pixels, masks = device_rescale_normalize(
                            pixels_u8, valid_hw, spec_q
                        )
                    return apply_post_q(
                        params, pixels, masks, target_sizes,
                        query_embeds, query_mask,
                    )

            else:
                forward_q = apply_post_q
            self._forward_q_fn = forward_q
        self._donate_pixels = donate_pixels
        self._jit_programs()

    def _jit_programs(self) -> None:
        """jit the forward(s) for the current placement; called again by
        `rebuild_degraded`, whose narrower mesh is a different program.

        One compiled program per batch bucket; jit caches by shape. Only
        the uint8 staging buffer that device_rescale_normalize consumes is
        donated (it is per-call scratch and freeing it keeps HBM headroom
        at large buckets). The host-float path's pixel tensor is NOT: XLA
        cannot alias it to any of the tiny postprocess outputs, so donating
        it frees nothing and emits a "Some donated buffers were not
        usable: float32[...]" warning on every call (pre-round record r05;
        ISSUE 5 satellite — tests/test_device_preprocess.py asserts the
        float path stays warning-free).
        """
        donate = (1,) if (self._donate_pixels and self.device_preprocess) else ()
        self._forward = jax.jit(
            self._data_parallel(self._forward_fn), donate_argnums=donate
        )
        self._forward_q = None
        if self._forward_q_fn is not None:
            self._forward_q = jax.jit(
                self._data_parallel(self._forward_q_fn, n_replicated=2),
                donate_argnums=donate,
            )

    def _data_parallel(self, fn, n_replicated: int = 0):
        """`fn(params, pixels, second, sizes, *replicated)` for this
        placement. On a dp-only mesh it runs under `shard_map`: each chip
        runs the one-chip program on its slice of the batch, params whole on
        every chip. That is all data parallelism means here, and it is the
        only form the chip's compiler takes for a program that holds a
        Pallas kernel — left to the SPMD partitioner, lowering stops with
        "Mosaic kernels cannot be automatically partitioned. Please wrap the
        call in a shard_map." With tp > 1 the partitioner still places the
        collectives, so a kernel-bearing program cannot serve tensor-parallel
        on a TPU yet (ROADMAP D8)."""
        if not self._shard_mapped:
            return fn
        batch, whole = P("dp"), P()
        return jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(whole, batch, batch, batch) + (whole,) * n_replicated,
            out_specs=batch,
            # the kernels' outputs carry no varying-axes annotation
            check_vma=False,
        )

    def _place(self, mesh, device, batch_buckets: Sequence[int]) -> None:
        """Bind params + input sharding + bucket ladder to a topology.

        Called at construction and again by `rebuild_degraded` — params are
        always re-placed from the host copy in `self.built.params`, so a
        rebuild never depends on state held by a dead device.
        """
        self.mesh = mesh
        if mesh is not None:
            from spotter_tpu.parallel.sharding import (
                check_rules_cover,
                data_sharding,
                shard_params,
            )

            if int(dict(mesh.shape).get("tp", 1)) > 1 and self.tp_rules:
                # fail-loud (ISSUE 13): a TP rule matching nothing means the
                # param tree drifted from the family's rule set — at real
                # model scale those weights would silently replicate and
                # blow the per-chip HBM ceiling tp exists to stay under
                check_rules_cover(
                    self.built.params, self.tp_rules,
                    family=self.built.model_name,
                )
            dp = mesh.shape["dp"]
            # every bucket must split evenly across dp shards: round UP so the
            # configured max batch capacity is kept, never shrunk
            batch_buckets = sorted({-(-b // dp) * dp for b in batch_buckets})
            self.batch_buckets = tuple(batch_buckets)
            self.device = None
            self.params = shard_params(self.built.params, mesh, self.tp_rules)
            self._in_sharding = data_sharding(mesh)
        else:
            self.batch_buckets = tuple(sorted(batch_buckets))
            self.device = device or jax.devices()[0]
            self.params = jax.device_put(self.built.params, self.device)
            self._in_sharding = self.device
        # the host staging buffers are sized by the ladder's largest rung,
        # so a re-place starts a free-list of its own
        self._slabs = StagingSlabs(
            self.batch_buckets[-1], self.built.preprocess_spec,
            self.device_preprocess, self.metrics,
        )
        # What this engine runs on, checked and said once per placement;
        # the same block answers /healthz. Re-run on every re-place so a
        # degraded rebuild's narrower device set is reflected there and in
        # the perf ledger's MFU math (peak-TFLOPs autodetect keys on
        # device_kind). The HBM gauges get one synchronous seed sample
        # (None-safe on CPU).
        self.device_info = describe_devices(self.devices())
        logger.info(
            "engine placed on platform=%(platform)s kind=%(device_kind)s "
            "count=%(count)d", self.device_info,
        )
        self.metrics.perf.set_device_info(
            self.device_info["device_kind"], self.device_info["count"]
        )
        sample_hbm_once(self.devices, self.metrics.perf)

    @property
    def _shard_mapped(self) -> bool:
        """True when the programs run under `_data_parallel`'s shard_map."""
        return self.mesh is not None and self.tp == 1

    @property
    def dp(self) -> int:
        """Data-parallel width the serving batch is sharded over (1 = single chip)."""
        return int(self.mesh.shape["dp"]) if self.mesh is not None else 1

    def weights_digest(self) -> str:
        """Structural fingerprint of the loaded weights (ISSUE 15): a
        digest over model name plus every param's path, shape and dtype.
        Cheap (no device reads) and stable across processes, it catches
        the deploy skew that matters for rollout identity — a different
        checkpoint architecture, head count, or quantization layout behind
        the same build tag. `SPOTTER_TPU_WEIGHTS_DIGEST` overrides it when
        byte-exact provenance is available from the weights pipeline."""
        import hashlib

        h = hashlib.sha256()
        h.update(str(getattr(self.built, "model_name", "")).encode())
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            self.built.params
        ):
            h.update(
                f"{jax.tree_util.keystr(path)}:"
                f"{tuple(getattr(leaf, 'shape', ()))}:"
                f"{getattr(leaf, 'dtype', '?')}".encode()
            )
        return h.hexdigest()[:12]

    def attest(self) -> dict:
        """On-device weights attestation (ISSUE 17): a jit'd bitpattern
        checksum reduction over every param shard, computed WHERE THE
        SHARD LIVES under dp×tp (the jit follows each shard's committed
        placement, so a single bad chip's copy is caught AND named), and
        compared against the trusted host checkpoint copy in
        `self.built.params` sliced identically via each shard's index.

        Bit-exact by construction: the checksum is an integer sum of the
        raw bit patterns mod 2^32 — order-independent (so dp/tp layout
        and reduction order cannot change it, unlike a float reduction)
        and sensitive to a single flipped bit. Only scalars cross the
        D2H boundary. Returns `{"ok", "checked", "mismatched",
        "observed", "expected"}` with per-device checksum maps.
        """
        per_device: dict[str, int] = {}
        expected: dict[str, int] = {}
        host_leaves = jax.tree_util.tree_leaves(self.built.params)
        for leaf, host_leaf in zip(
            jax.tree_util.tree_leaves(self.params), host_leaves
        ):
            host_arr = np.asarray(host_leaf)
            if host_arr.dtype != np.dtype(leaf.dtype):
                # placement may have cast (e.g. f64 checkpoint -> f32
                # device): attest what was actually placed
                host_arr = host_arr.astype(np.dtype(leaf.dtype))
            shards = getattr(leaf, "addressable_shards", None) or []
            if not shards:
                shards = [None]
            for sh in shards:
                if sh is None:
                    key = "device:?"
                    observed = int(_attest_sum(leaf))
                    host_slice = host_arr
                else:
                    key = f"device:{sh.device.id}"
                    observed = int(_attest_sum(sh.data))
                    host_slice = host_arr[sh.index]
                per_device[key] = (per_device.get(key, 0) + observed) % 2**32
                expected[key] = (
                    expected.get(key, 0) + _host_checksum(host_slice)
                ) % 2**32
        mismatched = sorted(
            k for k in per_device if per_device[k] != expected.get(k)
        )
        return {
            "ok": not mismatched,
            "checked": len(per_device),
            "mismatched": mismatched,
            "observed": per_device,
            "expected": expected,
        }

    def corrupt_weights(self, n: int) -> None:
        """Test-only SDC injection seam (faults.py corrupt_weights=<n>):
        flip one element in each of the first `n` DEVICE params. The host
        copy stays pristine — it is the attestation's trusted reference,
        exactly like a checkpoint on disk vs a corrupted restore."""
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        for i, leaf in enumerate(leaves[: max(int(n), 0)]):
            idx = (0,) * getattr(leaf, "ndim", 0)
            leaves[i] = leaf.at[idx].set(leaf[idx] + 1)
        self.params = jax.tree_util.tree_unflatten(treedef, leaves)

    @property
    def tp(self) -> int:
        """Tensor-parallel width the params are split over (1 = whole params
        on every chip)."""
        if self.mesh is None:
            return 1
        return int(dict(self.mesh.shape).get("tp", 1))

    def devices(self) -> list:
        """The devices this engine currently places work on."""
        if self.mesh is None:
            return [self.device]
        return list(self.mesh.devices.flat)

    def can_degrade(self) -> bool:
        """True when a fatal shard loss can be survived in place: dp-sharded
        (something to shrink) and tp=1 (params whole on every chip)."""
        return (
            self.mesh is not None
            and self.dp > 1
            and int(self.mesh.shape.get("tp", 1)) == 1
        )

    def probe_shards(self) -> list:
        """Shard health probe: a tiny per-device compute ping; returns the
        devices that answered. A dead/halted chip raises (or hangs inside
        the runtime's own deadline) instead of echoing the value back."""
        alive = []
        for d in self.devices():
            try:
                faults.on_shard_probe(d.id)
                x = jax.device_put(np.ones((8,), np.float32), d)
                jax.block_until_ready(x + 1.0)
                alive.append(d)
            except Exception:
                continue
        return alive

    def rebuild_degraded(self, alive_devices: Sequence) -> int:
        """Rebuild in place at the largest viable dp over `alive_devices`.

        4 -> 2 -> 1: halve the width until it fits the surviving shards,
        rescale the aggregate bucket ladder to keep the per-chip batch the
        ladder was tuned for, re-place params from the host copy, and
        re-warm every bucket so the first post-rebuild batch doesn't pay a
        compile. Bumps `generation`; detect() calls arriving mid-rebuild
        wait on the gate instead of racing the placement swap.
        """
        old_dp = self.dp
        if not alive_devices:
            raise FatalEngineError(
                f"no alive devices to rebuild on (was dp={old_dp})"
            )
        new_dp = old_dp
        while new_dp > len(alive_devices):
            new_dp //= 2
        if new_dp < 1:
            raise FatalEngineError(
                f"cannot fit any dp width on {len(alive_devices)} alive devices"
            )
        per_chip = sorted({max(1, b // old_dp) for b in self.batch_buckets})
        new_buckets = tuple(b * new_dp for b in per_chip)
        self._rebuild_gate.clear()
        try:
            from spotter_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(dp=new_dp, tp=1, devices=list(alive_devices)[:new_dp])
            self._place(mesh, None, new_buckets)
            self._jit_programs()
            with self._compile_source("rebuild"):
                self.warmup()
            # bumped only once the rescaled ladder is compiled and warm:
            # "generation advanced" means "serving again", so the
            # time-to-degraded measurement can't flatter itself
            self.generation += 1
            self.metrics.record_engine_rebuild(old_dp, self.dp)
        finally:
            self._rebuild_gate.set()
        return self.dp

    def bucket_for(self, n: int) -> int:
        for b in self.batch_buckets:
            if n <= b:
                return b
        return self.batch_buckets[-1]

    @contextmanager
    def _compile_source(self, source: str):
        """Tag compiles recorded while the context is active (thread-local:
        concurrent worker threads never see each other's provenance)."""
        prev = getattr(self._compile_src, "value", None)
        self._compile_src.value = source
        try:
            yield
        finally:
            self._compile_src.value = prev

    def _current_source(self) -> str:
        return getattr(self._compile_src, "value", None) or "traffic"

    def _shape_key(self, batch: int, h: int, w: int, qset=None) -> str:
        base = f"{'u8' if self.device_preprocess else 'f32'}:{batch}x{h}x{w}"
        if qset is not None:
            # the open-vocab forward is a distinct program per padded query
            # count — the compile ledger must not conflate it with the
            # closed-set program of the same pixel shape
            base += f":q{qset.embeds.shape[0]}"
        return base

    def _flops_of(self, abstract_args, fn=None) -> Optional[float]:
        """FLOPs of the compiled program for one input shape, from XLA's
        HLO cost analysis on the lowered (pre-compile) module — a re-trace,
        not a re-compile, so it is cheap enough to run once per shape
        inline. Called through `PerfLedger.flops_for`, which caches the
        result (failures included) per shape key. `fn` selects the program
        (default the closed-set forward; the open-vocab dispatch passes
        `_forward_q`)."""
        # pallas_call lowers to custom-call HLOs that cost_analysis may
        # count as 0 FLOPs (or fail on entirely) — collect the kernels'
        # self-reported analytic FLOPs during the trace and fold them in
        # (obs/perf.py `combine_flops`: FLOPs honesty, ISSUE 18)
        with perf_mod.collect_kernel_flops() as noted:
            lo = (fn or self._forward).lower(self.params, *abstract_args)
        ca = lo.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = ca.get("flops") if hasattr(ca, "get") else None
        total = perf_mod.combine_flops(flops, noted.get("__total__"))
        if total is not None and self._shard_mapped:
            # under `_data_parallel` the lowered module is ONE chip's
            # program, and every chip of the dp axis runs it
            total *= self.dp
        return total

    def warmup(self) -> None:
        """Compile every bucket ahead of traffic (first compile is slow).

        Each bucket's compile lands in the compile ledger (ISSUE 10) with
        its wall time and provenance (`warmup`, or `rebuild` when called
        from `rebuild_degraded`), and its program FLOPs are cost-analyzed
        into the MFU ledger so steady-state traffic never pays the
        lowering.
        """
        h, w = self.built.preprocess_spec.input_hw
        perf = self.metrics.perf
        source = self._current_source() if getattr(
            self._compile_src, "value", None
        ) else "warmup"
        for b in self.batch_buckets:
            # _put with the serving sharding so warmup compiles the exact
            # programs the traffic path will hit (no recompiles later)
            if self.device_preprocess:
                first = self._put(np.zeros((b, h, w, 3), np.uint8))
                second = self._put(np.tile(np.asarray([[h, w]], np.int32), (b, 1)))
            else:
                first = self._put(np.zeros((b, h, w, 3), np.float32))
                second = self._put(np.ones((b, h, w), np.float32))
            sizes = self._put(np.ones((b, 2), np.float32))
            key = self._shape_key(b, h, w)
            novel = perf.enabled and perf.compiles.record_dispatch(key)
            # abstract shapes captured before the call: the uint8 staging
            # buffer is donated, so the cost-analysis lowering below must
            # not touch the concrete arrays afterwards
            absargs = tuple(
                jax.ShapeDtypeStruct(a.shape, a.dtype)
                for a in (first, second, sizes)
            )
            # set-up phases (the `setup_phases_s` dict of /metrics): the
            # bucket's compile or cache load with its first run, then the
            # FLOPs ledger's second lowering
            with obs.span(f"setup.warmup.{key}") as compiled:
                jax.block_until_ready(
                    self._forward(self.params, first, second, sizes)
                )
            if novel:
                perf.compiles.record_compile(key, compiled.seconds, source)
                with obs.span(f"setup.flops.{key}"):
                    perf.flops_for(key, lambda a=absargs: self._flops_of(a))

    def _put(self, arr: np.ndarray):
        """Host array -> device(s), per-shard H2D overlap under a mesh.

        Mesh mode splits the host array into its per-device shards and
        dispatches one async copy per device instead of one monolithic
        device_put: shard k+1's upload overlaps shard k's, so the H2D wall
        time approaches the per-chip slice cost rather than the aggregate
        batch cost at dp>1.
        """
        if self.mesh is None:
            return jax.device_put(arr, self.device)
        try:
            idx_map = self._in_sharding.addressable_devices_indices_map(arr.shape)
            shards = [jax.device_put(arr[idx], d) for d, idx in idx_map.items()]
            return jax.make_array_from_single_device_arrays(
                arr.shape, self._in_sharding, shards
            )
        except (AttributeError, TypeError, KeyError, ValueError, NotImplementedError):
            # multi-host or API drift: the one-call path is correct. ONLY
            # shape/API mismatches fall through — a real per-shard H2D
            # failure is a RuntimeError (XlaRuntimeError) and must surface
            # to the failure classifier, not be silently retried as a
            # monolithic device_put that would hit the same dead chip.
            return jax.device_put(arr, self._in_sharding)

    def _put_rep(self, arr: np.ndarray):
        """Host array -> device(s), REPLICATED. The open-vocab query matrix
        must land whole on every chip (its leading axis is queries, not
        batch — `_put`'s dp sharding would split the vocabulary)."""
        if self.mesh is None:
            return jax.device_put(arr, self.device)
        from spotter_tpu.parallel.sharding import replicated

        return jax.device_put(arr, replicated(self.mesh))

    def detect(
        self,
        images: list[Image.Image],
        canvas_hw: Optional[tuple[int, int]] = None,
        qset=None,
    ) -> list[list[dict]]:
        """PIL images -> per-image lists of {"label", "score", "box"} dicts.

        Splits into bucket-sized chunks, pads the tail, strips pad results.

        Multi-chunk calls run a depth-2 pipeline (VERDICT r2 next #2): JAX
        dispatch is async, so chunk N+1's host staging (PIL decode/resize,
        normalize, device_put) and the D2H fetch of chunk N-1 both overlap
        chunk N's device compute instead of serializing with it. Single-chunk
        calls behave exactly as before (stage -> dispatch -> fetch). Across
        concurrent detect() calls (the MicroBatcher's worker threads), the
        H2D lock serializes stage-put + dispatch only — the blocking
        `jax.device_get` in `_finish` runs outside it, so the next batch's
        async `_put` overlaps the in-flight batch's device step
        (double-buffered H2D, ISSUE 9).

        `canvas_hw` (ragged batching, ISSUE 9): a (H, W) padded canvas for
        shortest_edge specs, smaller than the static bucket, chosen by the
        scheduler to minimize padded-pixel waste. None (the default, and
        always for fixed-size specs) stages to the static bucket — the
        exact pre-ragged program.

        Failure classification (ISSUE 4): device exceptions anywhere in the
        stage/dispatch/fetch chain are classified (engine/errors.py). A
        transient error (RESOURCE_EXHAUSTED) downgrades the chunk to the
        next-smaller bucket — split in half, retried once, serially — with
        no caller-visible failure; a fatal error (device lost / DATA_LOSS)
        raises `FatalEngineError` for the batcher's degraded-rebuild /
        controlled-exit path; plain model errors propagate unchanged so the
        batcher's poison bisect can isolate them per image.

        `qset` (open vocabulary, ISSUE 13): a `caching.text_cache.QuerySet`
        — the whole call detects against ITS vocabulary through the
        query-argument forward (`_forward_q`), labels mapped through
        `qset.id2label`. None (the default, and always for closed-set
        families) keeps the baked-constant forward bit-identical.
        """
        if qset is not None and self._forward_q is None:
            raise ValueError(
                f"{self.built.model_name} is a closed-set family: it has no "
                f"text encoder, so per-request `queries` are unsupported"
            )
        if not self._rebuild_gate.is_set():
            # a degraded rebuild is swapping placement under us: wait it out
            # rather than racing half-moved params (bounded by the watchdog
            # one layer up either way)
            self._rebuild_gate.wait(timeout=REBUILD_GATE_WAIT_S)
        results: list[list[dict]] = []
        max_b = self.batch_buckets[-1]
        chunks = [images[i : i + max_b] for i in range(0, len(images), max_b)]
        pending = None  # (dispatched_item, chunk_images)
        for chunk in chunks:
            try:
                dispatched = self._launch(chunk, canvas_hw, qset)
            except Exception as exc:
                # keep result order: finish the older in-flight chunk first,
                # then recover (or fail) this one
                if pending is not None:
                    results.extend(
                        self._finish_or_recover(*pending, canvas_hw, qset)
                    )
                    pending = None
                results.extend(self._recover_chunk(chunk, exc, canvas_hw, qset))
                continue
            if pending is not None:
                results.extend(self._finish_or_recover(*pending, canvas_hw, qset))
            pending = (dispatched, chunk)
        if pending is not None:
            results.extend(self._finish_or_recover(*pending, canvas_hw, qset))
        return results

    def _finish_or_recover(
        self, dispatched_item, images: list[Image.Image], canvas_hw=None,
        qset=None,
    ):
        try:
            return self._finish(dispatched_item)
        except Exception as exc:
            return self._recover_chunk(images, exc, canvas_hw, qset)

    def _recover_chunk(
        self, images: list[Image.Image], exc: Exception, canvas_hw=None,
        qset=None,
    ) -> list[list[dict]]:
        """Classify a failed chunk and recover when the taxonomy allows it."""
        kind = classify_engine_exception(exc)
        if kind is FatalEngineError:
            raise as_typed(exc)
        if kind is TransientEngineError:
            # bucket-downgrade retry, once: the halves land in the
            # next-smaller bucket, which is exactly the recovery for an
            # HBM-OOM at the top bucket. A second failure propagates typed.
            self.metrics.record_batch_retry()
            try:
                # compile-ledger provenance (ISSUE 10): the halves may land
                # in a bucket traffic never compiled — that compile is an
                # OOM-downgrade cost, not organic traffic churn
                with self._compile_source("oom_downgrade"):
                    if len(images) <= 1:
                        return self._detect_chunk(images, canvas_hw, qset)
                    mid = (len(images) + 1) // 2
                    return self._detect_chunk(
                        images[:mid], canvas_hw, qset
                    ) + self._detect_chunk(images[mid:], canvas_hw, qset)
            except Exception as retry_exc:
                raise as_typed(retry_exc) from retry_exc
        raise exc

    def _detect_chunk(
        self, images: list[Image.Image], canvas_hw=None, qset=None
    ) -> list[list[dict]]:
        """Serial stage -> dispatch -> fetch for one chunk (<= max bucket)."""
        return self._finish(self._launch(images, canvas_hw, qset))

    def _launch(
        self, images: list[Image.Image], canvas_hw=None, qset=None
    ) -> _Batch:
        """Stage one chunk on the host, upload it and dispatch its program.
        The starvation clock (engine/metrics.py) counts the batch as
        staging from here to the return of `_dispatch`, and as in flight
        from there to its fetch in `_finish`."""
        starvation = self.metrics.starvation
        starvation.move(staging=+1)
        try:
            batch = self._stage_host(images, canvas_hw, qset)
            self._upload_and_dispatch(batch)
        except BaseException:
            starvation.move(staging=-1)
            raise
        starvation.move(staging=-1, in_flight=+1)
        return batch

    def _stage_host(
        self, images: list[Image.Image], canvas_hw=None, qset=None
    ) -> _Batch:
        """Decode/preprocess half of staging: everything before the H2D.

        Device-preprocess mode produces uint8 pixels + a (B, 2) valid-region
        tensor (3 B/px of H2D) instead of float pixels + a full mask
        (16 B/px). Either way the batch's arrays are views of one leased
        slab (engine/staging.py) and each decode-pool task writes its image
        straight into its own row: the batch is written once. `canvas_hw`
        (ragged, ISSUE 9) shrinks the shortest_edge pad target; pad rows
        always fill to whatever canvas the real rows got, so one batch is
        one static shape.

        The `decode` stage is tiled by its children: `engine.preprocess_map`
        (the decode pool's map, one `engine.preprocess_image` per image
        inside it, on the pool's threads) and `engine.stack_pad` (what is
        left to the caller: the `bucket - n` pad rows and the sizes).
        """
        n = len(images)
        batch = _Batch(next(self._batch_seq), n, self.bucket_for(n), qset)
        traces, tags = obs.batch_traces(), batch.tags
        batch.total = obs.span("engine.batch", traces, **tags).start()
        spec = self.built.preprocess_spec
        if canvas_hw is not None and spec.mode != "shortest_edge":
            canvas_hw = None  # fixed/pad_square canvases ARE the signal
        h, w = spec.input_hw if canvas_hw is None else map(int, canvas_hw)
        mask_rows = not (self.device_preprocess or self._slabs.mask_is_ones)

        def one(job):  # -> (valid (h, w) or None, original (h, w))
            j, image = job
            with obs.span("engine.preprocess_image", obs.NO_TRACE, annotate=True,
                          cpu=True, batch=batch.seq):
                if self.device_preprocess:
                    return decode_resize_uint8(
                        image, spec, canvas_hw, out=pixels[j])[1:]
                dst = (pixels[j], second[j] if mask_rows else None)
                return None, preprocess_image(image, spec, canvas_hw, out=dst)[2]

        # slow_stage=decode:<ms> lands inside (obs.span's fault seam)
        with obs.span("engine.decode", traces, stage=obs.DECODE,
                      annotate=True, **tags) as decode:
            # from here the batch holds the lease, and only `_finish` ends it
            batch.slab = self._slabs.lease(batch.bucket, h, w)
            pixels, second = batch.slab.views(batch.bucket, h, w)
            with obs.span("engine.preprocess_map", traces, annotate=True, **tags):
                done = self._decode_pool.map(one, list(enumerate(images)))
            with obs.span("engine.stack_pad", traces, annotate=True, **tags):
                sizes = self._fill_pad_rows(pixels, second, done)
                batch.arrays = (pixels, second, sizes)
        batch.stages[obs.DECODE] = decode.seconds
        batch.meta = self._perf_meta(images, pixels, n, spec, qset)
        return batch

    def _fill_pad_rows(self, pixels, second, done: list) -> np.ndarray:
        """The caller's share of staging, after the pool's tasks have
        written the real rows: the `bucket - n` pad rows (zero pixels; a
        mask of ones, unless the slab's always is; the canvas as a pad
        row's valid region), the real rows' valid regions, and the `(B, 2)`
        sizes, which it returns. `done`: what each task returned."""
        n = len(done)
        sizes = np.ones((len(pixels), 2), np.float32)
        sizes[:n] = [orig_hw for _, orig_hw in done]
        pixels[n:] = 0
        if self.device_preprocess:
            second[:n] = [valid_hw for valid_hw, _ in done]
            second[n:] = pixels.shape[1:3]
        elif not self._slabs.mask_is_ones:
            second[n:] = 1.0
        return sizes

    def _perf_meta(self, images, pixels, n: int, spec, qset=None) -> Optional[dict]:
        """Per-dispatch efficiency accounting inputs (ISSUE 10): the shape
        key the compile ledger tracks, the padded pixel volume the program
        pays FLOPs for, and the valid pixel volume that carries signal
        (useful_mfu_pct's discount). None with the ledger off — the
        disabled path allocates nothing."""
        if not self.metrics.perf.enabled:
            return None
        b, ch, cw = pixels.shape[0], pixels.shape[1], pixels.shape[2]
        padded_px = b * ch * cw
        if spec.mode == "shortest_edge":
            valid_px = 0
            for im in images:
                rh, rw = shortest_edge_size(
                    (int(im.height), int(im.width)), spec.size[0], spec.size[1]
                )
                valid_px += min(rh, ch) * min(rw, cw)
        else:
            # fixed specs fill the canvas; pad_square approximately does
            valid_px = n * ch * cw
        return {
            "shape": self._shape_key(b, ch, cw, qset),
            "padded_px": padded_px,
            "valid_px": min(valid_px, padded_px),
        }

    def _upload_and_dispatch(self, batch: _Batch) -> None:
        """The `h2d` stage, then the dispatch. `_h2d_lock` is held across
        the puts and the dispatch, so uploads stay ordered while `_finish`
        (D2H) proceeds concurrently. The stage runs, as it always has, from
        the end of staging to the end of the puts, so it holds the wait for
        the lock: `engine.h2d_lock_wait` and `engine.put` tile it, and the
        dispatch follows it, in no stage."""
        traces, tags = obs.batch_traces(), batch.tags
        with obs.span("engine.h2d", traces, stage=obs.H2D, annotate=True,
                      **tags) as h2d:
            with obs.span("engine.h2d_lock_wait", traces, annotate=True, **tags):
                self._h2d_lock.acquire()
            try:
                with obs.span("engine.put", traces, annotate=True, **tags):
                    self._put_staged(batch)
            except BaseException:
                self._h2d_lock.release()
                raise
        batch.stages[obs.H2D] = h2d.seconds
        try:
            self._dispatch(batch)
        finally:
            self._h2d_lock.release()

    def _put_staged(self, batch: _Batch) -> None:
        """Upload half of staging: the async `_put`s (per-shard overlap
        under a mesh) plus the H2D accounting."""
        host_arrays, n, qset = batch.arrays, batch.n, batch.qset
        staged = tuple(self._put(a) for a in host_arrays)
        if qset is not None:
            # the query matrix replicates (its leading axis is queries, not
            # batch); tiny next to the pixel tensors, so the H2D accounting
            # ignores it
            staged = staged + (
                self._put_rep(qset.embeds), self._put_rep(qset.mask),
            )
        self.metrics.record_h2d_bytes(sum(a.nbytes for a in host_arrays), n)
        self.metrics.set_decode_queue_depth(self._decode_pool.queue_depth())
        batch.arrays = staged

    def _dispatch(self, batch: _Batch) -> None:
        """Async-dispatch the compiled forward; no host blocking (except a
        novel shape's compile, which the compile ledger times — ISSUE 10).
        Opens the batch's `device` stage, which `_finish` closes."""
        staged, n, meta, qset = batch.arrays, batch.n, batch.meta, batch.qset
        traces, tags = obs.batch_traces(), batch.tags
        # fault seam: a dead-shard or device-OOM injection raises here with
        # the same status markers the real runtime would embed
        faults.on_engine_dispatch(n, [d.id for d in self.devices()])
        perf = self.metrics.perf
        novel = meta is not None and perf.compiles.record_dispatch(meta["shape"])
        if meta is not None:
            # abstract shapes captured before the call (donation deletes
            # the staged uint8 buffer once the program runs)
            absargs = tuple(
                jax.ShapeDtypeStruct(a.shape, a.dtype) for a in staged
            )
        with obs.span("engine.dispatch", traces, annotate=True, **tags) as call:
            if qset is not None:
                outputs = self._forward_q(self.params, *staged)
            else:
                outputs = self._forward(self.params, *staged)
        batch.device = obs.span(
            "engine.device", traces, stage=obs.DEVICE, **tags
        ).start()
        # the program holds its inputs for as long as it needs them; the
        # batch must not, or a staged batch lives on the device until its
        # answers are fetched
        batch.arrays = ()
        if novel:
            # first call of a shape blocks on trace+compile; its wall time
            # IS the serving stall a recompile storm multiplies
            perf.compiles.record_compile(
                meta["shape"], call.seconds, self._current_source()
            )
        if meta is not None:
            fwd = self._forward_q if qset is not None else self._forward
            meta["flops"] = perf.flops_for(
                meta["shape"], lambda a=absargs, f=fwd: self._flops_of(a, f)
            )
        # queue the D2H copies now: they start the moment compute finishes,
        # overlapping the next chunk's staging instead of its fetch
        for arr in outputs:
            arr.copy_to_host_async()
        batch.outputs = outputs

    def _finish(self, batch: _Batch) -> list[list[dict]]:
        """Block on the fetch, threshold on host, record metrics.

        Stage vocabulary is obs.STAGES everywhere (ISSUE 7 satellite):
        decode = decode-pool host work, each task writing its row of the
        leased slab, and the caller's pad rows, h2d =
        device_put enqueue (lock wait included), device = dispatch ->
        data-on-host (under pipelining the next chunk's host staging runs
        inside this span, but so does this chunk's compute — measuring from
        the end of the puts would bill the neighbor's staging as device
        time), postprocess = threshold and boxes. Each is a span that was
        open while the stage ran; `batch.stages` holds their seconds."""
        n, meta, qset = batch.n, batch.meta, batch.qset
        traces, tags = obs.batch_traces(), batch.tags
        try:
            with obs.span("engine.device_wait", traces, annotate=True, **tags):
                faults.sleep_stage(obs.DEVICE)  # slow_stage=device:<ms>
                scores, labels, boxes, *counters = jax.device_get(batch.outputs)
        finally:
            batch.device.stop()
            self.metrics.starvation.move(in_flight=-1)
        # the outputs are on the host, so the program has consumed its
        # inputs and nothing reads the slab any more: the lease ends here,
        # and nowhere else (a batch that failed before this line drops its
        # slab, which a pool task or a transfer may still be touching)
        self._slabs.release(batch.slab)
        batch.slab = None
        batch.stages[obs.DEVICE] = batch.device.seconds
        with obs.span("engine.postprocess", traces, stage=obs.POSTPROCESS,
                      annotate=True, **tags) as post:
            # open-vocab dispatches label against THEIR vocabulary (padded
            # query slots carry NEG_INF logits, so the argmax never lands
            # on one)
            id2label = qset.id2label if qset is not None else self.built.id2label
            out = [
                to_detections(
                    scores[j], labels[j], boxes[j], id2label, self.threshold
                )
                for j in range(n)
            ]
            # output-integrity chaos seam (ISSUE 17): sdc=<pct> perturbs
            # this share of answers into plausible garbage — the hook is
            # identity (one None check) when no plan is active
            out = [
                faults.corrupt_detections(dets, self.metrics.replica_id)
                for dets in out
            ]
            if counters:  # already on the host: the fetch above brought them
                self.metrics.record_program_counters(
                    {name: counter[:n] for name, counter in zip(self._counter_names, counters)})
        batch.stages[obs.POSTPROCESS] = post.seconds
        batch.total.stop()
        self.metrics.record_batch(
            n,
            batch.total.seconds,
            stages=batch.stages,
            trace_id=obs.batch_trace_id(),
            bucket=batch.bucket,
        )
        if meta is not None:
            # device-efficiency ledger (ISSUE 10): this dispatch's device
            # window, program FLOPs, and padded/valid pixel split — the
            # MFU / useful-MFU / duty-cycle inputs. The trace id makes the
            # top-K expensive-dispatch table joinable against the flight
            # recorder (/debug/perf -> /debug/traces).
            self.metrics.perf.record_dispatch(
                device_s=batch.device.seconds,
                batch=n,
                padded_px=meta.get("padded_px"),
                valid_px=meta.get("valid_px"),
                flops=meta.get("flops"),
                trace_id=obs.batch_trace_id(),
                shape=meta.get("shape"),
            )
        return out
