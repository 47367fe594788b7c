"""One scheduling core for the micro-batcher (ISSUE 9).

Before this module, `engine/batcher.py` interleaved three scheduling
concerns through one positional queue tuple: per-bucket FIFO fill, dp
superbatch sizing (an aggregate fill target), and the cache tier's keyed
coalescing at admission. This module collapses them onto one `Scheduler`
whose inputs are plain `QueueItem`s — a dp superbatch is just a bigger
fill target, a coalesced submit never becomes an item at all, and the
dispatch policy is a pure function over the pending items.

Two policies share the core:

- **FIFO (default, bit-identical to the pre-ISSUE-9 batcher):** the pack
  is the first `target` items in arrival order, padded to the engine's
  static bucket. `SPOTTER_TPU_RAGGED` unset selects this policy and the
  engine is called exactly as before (no canvas argument), so serving
  semantics do not move.

- **Ragged (`SPOTTER_TPU_RAGGED=1`, opt-in):** mixed-size images pack
  into ONE padded superbatch over the uint8 + `(B, 2)` valid-dims
  substrate that ships since PR 3 (Ragged Paged Attention's
  pack-irregular-work-into-one-dense-dispatch idea applied to vision).
  Admission is ordered by **deadline slack** rather than arrival — slo
  traffic (PR 8's request classes) fills the next dispatch first, bulk
  backfills the remainder — and the pack is built full-fill min-growth:

  1. **Mandatory tier:** deadline-carrying items whose slack has shrunk
     to `SPOTTER_TPU_RAGGED_URGENT_MS` (default 100) enter in slack
     order unconditionally — an urgent request is never displaced by a
     better-packing neighbor.
  2. **Seed:** with no urgent items, the highest-priority pending item
     seeds the pack, so the oldest work always dispatches (no
     starvation: every plan removes the current head).
  3. **Backfill:** remaining capacity fills from the priority-ordered
     pool, preferring items that FIT the current snapped canvas; only
     when nothing fits does the canvas grow, and then by the item that
     grows it least (priority breaks ties).

  Packs always fill to the dispatch target when the pending buffer can —
  a dispatch's cost for a conv model is `padded_batch x canvas_area`
  FLOPs whether slots are full or empty, so splitting a full bucket into
  two runt packs at smaller canvases is almost never a win (measured:
  the fragmentation cascade loses ~18% goodput; full-fill min-growth
  gains it back plus the canvas win).

Canvas shapes snap to multiples of `SPOTTER_TPU_RAGGED_STEP` (default
128, capped at the spec's static bucket) so the number of compiled
programs stays bounded: at the DETR serving bucket (1333x1333) that is
at most ~11x11 canvas shapes per batch bucket, and in practice traffic
concentrates on a few rungs. Only `shortest_edge` specs (the DETR
family) have a variable valid region to exploit; `fixed`-size specs
(RT-DETR, OWL-ViT) still get slack ordering but keep their one static
canvas.
"""

import os
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

from spotter_tpu.serving.overload import SLO

RAGGED_ENV = "SPOTTER_TPU_RAGGED"
RAGGED_STEP_ENV = "SPOTTER_TPU_RAGGED_STEP"
DEFAULT_RAGGED_STEP = 128
# the coarsest feature level of the shortest_edge families: a ResNet's
# total stride is 32, and Deformable DETR adds a stride-2 level on top
COARSEST_FEATURE_STRIDE = 64
RAGGED_URGENT_MS_ENV = "SPOTTER_TPU_RAGGED_URGENT_MS"
DEFAULT_RAGGED_URGENT_MS = 100.0

# how far past the fill target the ragged pump looks ahead for packing
# choice: a 2x window lets a same-shape backfill displace a canvas-growing
# straggler without holding anything longer than one dispatch
LOOKAHEAD_FACTOR = 2


def ragged_enabled() -> bool:
    return os.environ.get(RAGGED_ENV, "0").strip() not in ("", "0")


def ragged_step() -> int:
    """The canvas grid of a deployment. It has to be a multiple of
    `COARSEST_FEATURE_STRIDE`: the models take a token's validity from the
    pixel mask by nearest sampling (`models/detr.py:
    nearest_downsample_mask`, index `j * canvas // tokens`), so only on a
    canvas that is a whole number of the coarsest level's strides does
    token `j` read pixel `j * stride` whatever the canvas. Canvases on the
    grid then give an image the same valid tokens, and so does the full
    bucket where it is on the grid itself; on any other canvas a token at
    the valid region's edge turns valid or invalid with the canvas, and the
    same image answers differently by which batch it rode in: 25 px and
    flipped labels where a canvas on the grid differs by rounding
    (`tests/test_ragged.py::
    test_ragged_canvas_parity_vs_per_bucket_reference`). DETR's published
    bucket of 1333 is not on the grid (42 tokens, sampled every 31.7
    pixels), so there the full bucket's own token edges lie up to 10
    pixels from a sub-canvas's: ROADMAP R3."""
    raw = os.environ.get(RAGGED_STEP_ENV, "").strip()
    try:
        step = int(raw) if raw else DEFAULT_RAGGED_STEP
    except ValueError:
        raise ValueError(f"{RAGGED_STEP_ENV} must be an integer, got {raw!r}")
    if step < 1 or step % COARSEST_FEATURE_STRIDE:
        raise ValueError(
            f"{RAGGED_STEP_ENV} must be a positive multiple of "
            f"{COARSEST_FEATURE_STRIDE} (the coarsest feature level's "
            f"stride), got {step}"
        )
    return step


@dataclass
class QueueItem:
    """One queued unit of engine work (ISSUE 9 satellite: replaces the
    positional `(image, fut, deadline, trace, t_submit, adm)` tuple the
    scheduler, revocation stack, and coalescing paths all indexed into).

    `deadline` is None for keyed (coalesced) entries — the shared primary
    must outlive any single waiter's budget; waiters bound their own
    awaits. `dims` caches the image's post-resize valid (h, w) so the
    ragged policy computes it once per item, not once per plan.
    """

    image: object  # PIL.Image (duck-typed: scheduler only reads .height/.width)
    fut: object  # asyncio.Future
    deadline: Optional[object] = None  # resilience.Deadline
    trace: Optional[object] = None  # obs.Trace
    t_submit: float = 0.0
    adm: Optional[object] = None  # overload.Admission
    cls: str = SLO
    key: Optional[str] = None
    # Tenant identity (ISSUE 19): stamped at submit so the scheduler's
    # deficit-weighted round-robin and the per-tenant SLO accounting know
    # who each queued image belongs to (None = tenancy unconfigured).
    tenant: Optional[str] = None
    dims: Optional[tuple[int, int]] = field(default=None, compare=False)
    # Open-vocabulary query set (ISSUE 13): a caching.text_cache.QuerySet.
    # Its `key` is this item's batch-compatibility GROUP — the engine's
    # open-vocab program is specialized per query set, so a pack must never
    # mix two groups (None = the closed-set default group).
    qset: Optional[object] = field(default=None, compare=False)

    @property
    def group(self) -> Optional[str]:
        return self.qset.key if self.qset is not None else None


@dataclass
class PackPlan:
    """One dispatch: the packed items, the padded canvas they stage into
    (None = the spec's static bucket, i.e. the pre-ragged behavior), and
    the pack's padded-pixel waste for /metrics."""

    items: list[QueueItem]
    canvas_hw: Optional[tuple[int, int]] = None
    padding_waste_pct: Optional[float] = None


class Scheduler:
    """Dispatch policy over pending `QueueItem`s. Stateless between plans
    except for the spec/step configuration — the batcher owns the pending
    buffer and hands it in by reference (chosen items are removed)."""

    def __init__(
        self,
        spec=None,
        ragged: bool = False,
        step: Optional[int] = None,
        urgent_ms: Optional[float] = None,
        tenancy=None,
    ) -> None:
        # Fair scheduling (ISSUE 19): with a serving.tenancy.TenantPlane
        # attached, within-class ordering becomes deficit-weighted
        # round-robin across active tenants. None (the default, and every
        # unconfigured deployment) leaves every code path bit-identical.
        self.tenancy = tenancy
        self.spec = spec
        self.step = step if step is not None else ragged_step()
        if urgent_ms is None:
            raw = os.environ.get(RAGGED_URGENT_MS_ENV, "").strip()
            urgent_ms = float(raw) if raw else DEFAULT_RAGGED_URGENT_MS
        self.urgent_ms = urgent_ms
        # only shortest_edge specs have a variable valid region; a spec-less
        # engine (stub/synthetic: no `.built`) is treated as fully ragged —
        # its canvas is the items' own dims (the tests' recording engines)
        self.canvas_capable = spec is None or getattr(spec, "mode", None) == (
            "shortest_edge"
        )
        self.ragged = bool(ragged)

    @classmethod
    def from_env(cls, engine) -> "Scheduler":
        spec = getattr(getattr(engine, "built", None), "preprocess_spec", None)
        return cls(spec=spec, ragged=ragged_enabled())

    @property
    def fifo(self) -> bool:
        return not self.ragged

    def gather_target(self, target: int) -> int:
        """How many items the pump should hold before planning: exactly the
        fill target under FIFO (bit-identical drain), a lookahead window
        under ragged so the pack has displacement choices."""
        return target if self.fifo else target * LOOKAHEAD_FACTOR

    def item_dims(self, item: QueueItem) -> tuple[int, int]:
        """Post-resize valid (h, w) of an item — the pixels that actually
        carry signal once staged. Cached on the item."""
        if item.dims is not None:
            return item.dims
        spec = self.spec
        if spec is None:
            dims = (int(item.image.height), int(item.image.width))
        elif spec.mode == "shortest_edge":
            from spotter_tpu.ops.preprocess import shortest_edge_size

            dims = shortest_edge_size(
                (int(item.image.height), int(item.image.width)),
                spec.size[0],
                spec.size[1],
            )
        else:  # fixed / pad_square: every image fills the static canvas
            dims = spec.input_hw
        item.dims = dims
        return dims

    def priority_key(self, item: QueueItem, now: float):
        """Deadline-slack ordering (ISSUE 9): slo before bulk, then least
        slack first (no deadline = infinite slack), then arrival order."""
        slack = (
            item.deadline.remaining() if item.deadline is not None
            else float("inf")
        )
        return (0 if item.cls == SLO else 1, slack, item.t_submit)

    def _tenant_order(self, items: list) -> list:
        """DRR across the tenants present in `items` (ISSUE 19). Returns
        the INPUT LIST itself — not a copy — when tenancy is off or only
        one tenant is present, so the FIFO bit-identity contract reduces
        to object identity the tests can assert."""
        if self.tenancy is None or len(items) <= 1:
            return items
        return self.tenancy.drr_order(items, lambda it: it.tenant)

    def _classwise_tenant_order(self, items: list) -> list:
        """Apply DRR WITHIN each request class: the slo-before-bulk and
        slack orderings stay structural (overload.py's contract); only the
        ordering among same-class items of different tenants changes."""
        if self.tenancy is None:
            return items
        slo = [it for it in items if it.cls == SLO]
        bulk = [it for it in items if it.cls != SLO]
        o_slo = self._tenant_order(slo)
        o_bulk = self._tenant_order(bulk)
        if o_slo is slo and o_bulk is bulk:
            return items
        return list(o_slo) + list(o_bulk)

    def _full_canvas(self) -> Optional[tuple[int, int]]:
        return self.spec.input_hw if self.spec is not None else None

    def _snap(self, hw: tuple[int, int]) -> tuple[int, int]:
        """Round a canvas up to the step grid, capped at the static bucket
        (the compile-count bound)."""
        cap = self._full_canvas()
        out = []
        for i, d in enumerate(hw):
            s = -(-d // self.step) * self.step
            if cap is not None:
                s = min(s, cap[i])
            out.append(max(s, d if cap is None else min(d, cap[i])))
        return (out[0], out[1])

    @staticmethod
    def _waste_pct(dims: Sequence[tuple[int, int]], canvas: tuple[int, int]) -> float:
        area = canvas[0] * canvas[1]
        if not dims or area <= 0:
            return 0.0
        valid = sum(h * w for h, w in dims)
        return 100.0 * (1.0 - valid / (len(dims) * area))

    @staticmethod
    def _padded_batch(n: int, buckets: Optional[Sequence[int]]) -> int:
        """The batch size the engine will actually pad `n` items to."""
        if not buckets:
            return n
        for b in sorted(buckets):
            if n <= b:
                return b
        return max(buckets)

    def plan(
        self,
        pending: list[QueueItem],
        target: int,
        now: Optional[float] = None,
        buckets: Optional[Sequence[int]] = None,
    ) -> PackPlan:
        """Pick (and remove from `pending`) the next dispatch's pack.

        FIFO: the first `target` items in arrival order — the exact
        pre-ISSUE-9 batch — with `canvas_hw=None` so the engine stages to
        its static bucket; padded-pixel waste is still measured against
        that bucket so the per-bucket baseline is observable.

        Ragged: full-fill min-growth over the deadline-slack ordering —
        urgent deadline items (slack <= `urgent_ms`) enter unconditionally,
        the highest-priority item seeds otherwise, and backfill prefers
        items that fit the current snapped canvas before growing it by the
        least-growing item. The pack always fills to `target` when the
        buffer can: a dispatch costs padded_batch x canvas_area FLOPs
        whether its slots are full or not (`buckets` documents the ladder
        the engine pads to), so runt packs are wasted calls.

        Query-group isolation (ISSUE 13): the engine's open-vocab program is
        specialized per query set, so a pack only ever draws from ONE
        `QueueItem.group`. The group is the leader's (queue head under FIFO,
        highest-priority item under ragged); other groups stay pending and
        lead the next plan — the delay window bounds their extra wait
        exactly like any leftover. With a single group in the buffer (the
        closed-set default: every group None) this path is untaken and the
        plan is bit-identical to the pre-ISSUE-13 policy.
        """
        target = max(1, target)
        if len({it.group for it in pending}) > 1:
            now = time.monotonic() if now is None else now
            if self.fifo:
                group = pending[0].group
            else:
                group = min(
                    pending, key=lambda it: self.priority_key(it, now)
                ).group
            selected = [it for it in pending if it.group == group]
            plan = self._plan_from(selected, target, now, buckets)
            chosen = {id(it) for it in plan.items}
            pending[:] = [it for it in pending if id(it) not in chosen]
            return plan
        return self._plan_from(pending, target, now, buckets)

    def _plan_from(
        self,
        pending: list[QueueItem],
        target: int,
        now: Optional[float] = None,
        buckets: Optional[Sequence[int]] = None,
    ) -> PackPlan:
        """The single-group policy body (see `plan`); mutates `pending`."""
        if self.fifo:
            ordered = self._tenant_order(pending)
            if ordered is pending:
                # tenancy off / single tenant: the EXACT pre-ISSUE-19
                # drain — same statements, same object identities
                pack = pending[:target]
                del pending[: len(pack)]
            else:
                pack = ordered[:target]
                chosen = {id(it) for it in pack}
                pending[:] = [it for it in pending if id(it) not in chosen]
            full = self._full_canvas()
            waste = (
                self._waste_pct([self.item_dims(it) for it in pack], full)
                if full is not None and pack
                else None
            )
            return PackPlan(pack, None, waste)

        now = time.monotonic() if now is None else now
        items = sorted(pending, key=lambda it: self.priority_key(it, now))
        items = self._classwise_tenant_order(items)

        if not self.canvas_capable:
            # fixed-canvas spec: slack ordering only, static canvas
            pack = items[:target]
            full = self._full_canvas()
            chosen = {id(it) for it in pack}
            pending[:] = [it for it in pending if id(it) not in chosen]
            waste = (
                self._waste_pct([self.item_dims(it) for it in pack], full)
                if full is not None and pack
                else None
            )
            return PackPlan(pack, None, waste)

        # mandatory tier: urgent deadline items, in slack order
        pack: list[QueueItem] = []
        pool: list[QueueItem] = []
        for it in items:
            if (
                len(pack) < target
                and it.deadline is not None
                and it.deadline.remaining() * 1000.0 <= self.urgent_ms
            ):
                pack.append(it)
            else:
                pool.append(it)
        if not pack and pool:
            pack.append(pool.pop(0))  # seed: the highest-priority item
        run_h = max((self.item_dims(it)[0] for it in pack), default=0)
        run_w = max((self.item_dims(it)[1] for it in pack), default=0)

        # backfill: fit-first in priority order, then least-growth
        while len(pack) < target and pool:
            ch, cw = self._snap((run_h, run_w))
            fit_idx = None
            grow_idx = None
            grow_area = None
            for i, it in enumerate(pool):
                h, w = self.item_dims(it)
                if h <= ch and w <= cw:
                    fit_idx = i
                    break
                gh, gw = self._snap((max(run_h, h), max(run_w, w)))
                if grow_area is None or gh * gw < grow_area:
                    grow_idx, grow_area = i, gh * gw
            pick = fit_idx if fit_idx is not None else grow_idx
            it = pool.pop(pick)
            h, w = self.item_dims(it)
            run_h, run_w = max(run_h, h), max(run_w, w)
            pack.append(it)

        canvas = self._snap((run_h, run_w))
        chosen = {id(it) for it in pack}
        pending[:] = [it for it in pending if id(it) not in chosen]
        return PackPlan(
            pack,
            canvas,
            self._waste_pct([self.item_dims(it) for it in pack], canvas),
        )
