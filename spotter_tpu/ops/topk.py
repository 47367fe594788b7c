"""Exact top-k without the big sort — TPU radix-bisect selection.

Computes the IDENTICAL result to `jax.lax.top_k` (values sorted descending,
ties by lower index — the documented lax.top_k contract) from three pieces:

1. radix bisection of the k-th largest value: 32 monotone-key threshold
   counts (compare + row-sum over (B, S), one per bit) instead of a sort —
   the float-to-ordered-uint trick makes bitwise binary search exact;
2. mask compaction: the selected positions' indices scatter into k slots by
   their prefix-sum rank (index order == lax.top_k's tie order);
3. a final k-element lax.top_k to produce score-descending order — tiny
   (k x k) compared to the S-wide sort it replaces.

NaN caveat: the monotone key orders NaN above +inf (sign-magnitude view)
instead of lax.top_k's NaN semantics; detection scores are finite logits.

Measured (pre-round, loop-in-jit, (8, 8400) k=300): lax.top_k
0.51 ms/iter vs bisect 0.94 ms/iter — the compaction scatter + cumsums cost
more than XLA's sort at these shapes, so `auto` keeps lax everywhere and
bisect stays an opt-in for re-evaluation at wider S or larger batch
(threshold search alone is 0.52 ms and scales O(S) vs the sort's
O(S log S)).

`SPOTTER_TPU_TOPK` = auto (currently always lax) | lax | bisect.
"""

import os

import jax
import jax.numpy as jnp

TOPK_ENV = "SPOTTER_TPU_TOPK"


def _mode() -> str:
    name = os.environ.get(TOPK_ENV, "auto").strip().lower()
    if name not in ("auto", "lax", "bisect"):
        raise ValueError(f"{TOPK_ENV} must be auto|lax|bisect, got {name!r}")
    return name


def _ordered_key(x: jnp.ndarray) -> jnp.ndarray:
    """Monotone float32 -> uint32 map: a > b  <=>  key(a) > key(b)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    neg = bits >= jnp.uint32(0x80000000)
    return jnp.where(neg, ~bits, bits | jnp.uint32(0x80000000))


def bisect_top_k(scores: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(B, S) scores -> (values (B, k) desc, indices (B, k) int32); exact
    lax.top_k semantics (see module docstring for the NaN caveat)."""
    b, s = scores.shape
    if k >= s:
        return jax.lax.top_k(scores, k)
    scores_f = scores.astype(jnp.float32)
    key = _ordered_key(scores_f)

    # radix-select the k-th largest key: build the threshold MSB-first
    def body(i, t):
        cand = t | (jnp.uint32(1) << (31 - i))
        cnt = (key >= cand[:, None]).sum(axis=1)
        return jnp.where(cnt >= k, cand, t)

    kth = jax.lax.fori_loop(0, 32, body, jnp.zeros((b,), jnp.uint32))

    gt = key > kth[:, None]
    eq = key == kth[:, None]
    need = k - gt.sum(axis=1, keepdims=True)
    sel = gt | (eq & (jnp.cumsum(eq, axis=1) <= need))

    # compact selected indices into k slots in ascending-index order
    rank = jnp.cumsum(sel, axis=1)  # 1-based among selected
    pos = jnp.where(sel, rank - 1, k)  # unselected -> trash slot k
    bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s))
    sidx = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    idx_by_index = (
        jnp.zeros((b, k + 1), jnp.int32).at[bidx, pos].set(sidx, mode="drop")[:, :k]
    )

    # order the k winners by score; the stable small sort keeps lower-index
    # ties first because idx_by_index is ascending
    vals = jnp.take_along_axis(scores_f, idx_by_index, axis=1)
    vals_sorted, order = jax.lax.top_k(vals, k)
    idx_sorted = jnp.take_along_axis(idx_by_index, order, axis=1)
    return vals_sorted.astype(scores.dtype), idx_sorted


def top_k(scores: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in lax.top_k for 2-D (B, S); SPOTTER_TPU_TOPK=bisect opts into
    the radix path (measured slower at R101 shapes — module docstring)."""
    if _mode() == "bisect":
        return bisect_top_k(scores, k)
    return jax.lax.top_k(scores, k)
