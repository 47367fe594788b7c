"""Text-embedding cache for open-vocabulary detection (ISSUE 13).

OWL-ViT's text tower is the expensive half of an open-vocab request that the
closed-set serving path never pays: at ViT-L scale one vocabulary encode is
tens of milliseconds of device time. Vocabularies repeat heavily (a tenant
reuses its label set on every image), so the resolver memoizes encoded query
sets keyed `model|sha256(sorted queries)` (caching/keys.py) — a repeated
vocabulary costs one dict lookup and no encode
(`tests/test_openvocab.py::test_resolver_caches_and_pads`).

The cached value is a `QuerySet`: labels in canonical (sorted) order, the
normalized (Q_pad, proj) embedding matrix PADDED to a bucketed query count
(`SPOTTER_TPU_QUERY_PAD`, default 8) with a validity mask, so the number of
compiled engine programs is bounded by distinct PAD MULTIPLES, not distinct
vocabulary sizes. `QuerySet.key` doubles as the scheduler's batch-group id:
the engine forward is shape- and constant-specialized per query set, so the
batcher must never mix two vocabularies into one dispatch.

Thread-safe like ResultCache (resolve runs in an executor off the event
loop); entry count is bounded (`SPOTTER_TPU_TEXT_CACHE_ENTRIES`, LRU).
"""

import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from spotter_tpu.caching.keys import queries_digest, queries_key

QUERY_PAD_ENV = "SPOTTER_TPU_QUERY_PAD"
DEFAULT_QUERY_PAD = 8
TEXT_CACHE_ENTRIES_ENV = "SPOTTER_TPU_TEXT_CACHE_ENTRIES"
DEFAULT_TEXT_CACHE_ENTRIES = 256


@dataclass(frozen=True)
class QuerySet:
    """One resolved open-vocabulary query set, engine-ready.

    `embeds` is (Q_pad, proj) float32 with rows past `len(labels)` zeroed;
    `mask` is (Q_pad,) int32 1=real query. Padded slots carry NEG_INF logits
    through the class head, so they can never win the per-patch argmax.
    """

    key: str  # queries_key(model, queries) — also the scheduler group id
    digest: str  # sha256 over the sorted queries (result-cache key suffix)
    labels: tuple  # canonical sorted query strings, index == label id
    embeds: np.ndarray
    mask: np.ndarray

    @property
    def id2label(self) -> dict[int, str]:
        return dict(enumerate(self.labels))


def query_pad() -> int:
    raw = os.environ.get(QUERY_PAD_ENV, "").strip()
    try:
        pad = int(raw) if raw else DEFAULT_QUERY_PAD
    except ValueError:
        raise ValueError(f"{QUERY_PAD_ENV} must be an integer, got {raw!r}")
    return max(1, pad)


class TextQueryResolver:
    """queries -> QuerySet through the memoized text encoder.

    `encoder` is `BuiltDetector.text_encoder` (list[str] -> (Q, proj)
    float32). `metrics` (engine Metrics) gets hit/miss counts and encode
    wall times so the cache's win is observable in /metrics.
    """

    def __init__(
        self,
        model_name: str,
        encoder: Callable,
        metrics=None,
        max_entries: Optional[int] = None,
        pad: Optional[int] = None,
    ) -> None:
        self.model_name = model_name
        self.encoder = encoder
        self.metrics = metrics
        if max_entries is None:
            raw = os.environ.get(TEXT_CACHE_ENTRIES_ENV, "").strip()
            max_entries = int(raw) if raw else DEFAULT_TEXT_CACHE_ENTRIES
        self.max_entries = max(1, max_entries)
        self.pad = pad if pad is not None else query_pad()
        self._lock = threading.Lock()
        self._entries: OrderedDict[str, QuerySet] = OrderedDict()

    def resolve(self, queries) -> QuerySet:
        """The memoized encode. Raises ValueError on an empty query set.

        Holding the lock across the encode serializes concurrent misses for
        DIFFERENT keys too — deliberate: the encoder runs the model's text
        tower, and two towers racing on one device buys nothing. A hit
        never waits on an in-flight miss's device time beyond the lock.
        """
        t0 = time.monotonic()
        labels = tuple(sorted(str(q).strip() for q in queries if str(q).strip()))
        if not labels:
            raise ValueError("queries must contain at least one non-empty string")
        key = queries_key(self.model_name, labels)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self._record(True, (time.monotonic() - t0) * 1000.0)
                return entry
            embeds = np.asarray(self.encoder(list(labels)), np.float32)
            q, d = embeds.shape
            q_pad = -(-q // self.pad) * self.pad
            padded = np.zeros((q_pad, d), np.float32)
            padded[:q] = embeds
            mask = np.zeros((q_pad,), np.int32)
            mask[:q] = 1
            entry = QuerySet(
                key=key,
                digest=queries_digest(labels),
                labels=labels,
                embeds=padded,
                mask=mask,
            )
            self._entries[key] = entry
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
            self._record(False, (time.monotonic() - t0) * 1000.0)
            return entry

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": True,
                "entries": len(self._entries),
                "max_entries": self.max_entries,
                "query_pad": self.pad,
            }

    def _record(self, hit: bool, encode_ms: Optional[float]) -> None:
        if self.metrics is not None:
            try:
                self.metrics.record_text_cache(hit, encode_ms)
            except Exception:
                pass
