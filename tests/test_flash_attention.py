"""Flash-attention cutover in MultiHeadAttention.

Long unmasked self-attention routes to the Pallas TPU flash kernel
(models/layers.py); the naive path materializes (B, H, S, S) scores, which
at ViT-detector sequence lengths (yolos-base: 4300 tokens) is HBM-bound by
~7 GB of scores per batch-8 forward. CPU keeps the naive fused-XLA path, so
the parity test against it runs on real TPU only.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spotter_tpu.models.layers import FLASH_ATTN_MIN_SEQ, MultiHeadAttention


def _mha_outputs(seq, backend_force_naive, seed=0):
    import spotter_tpu.models.layers as layers_mod

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((2, seq, 64)), jnp.float32)
    pos = jnp.asarray(rng.standard_normal((1, seq, 64)), jnp.float32)
    mha = MultiHeadAttention(embed_dim=64, num_heads=4)
    params = mha.init(jax.random.PRNGKey(0), x, pos)

    if backend_force_naive:
        orig = layers_mod._FLASH_ATTN_ENABLED
        layers_mod._FLASH_ATTN_ENABLED = False
        try:
            return jax.jit(lambda p, a, b: mha.apply(p, a, b))(params, x, pos)
        finally:
            layers_mod._FLASH_ATTN_ENABLED = orig
    return jax.jit(lambda p, a, b: mha.apply(p, a, b))(params, x, pos)


def test_short_sequences_never_use_flash():
    """AIFI/decoder-length sequences stay on the reference path everywhere."""
    assert 400 < FLASH_ATTN_MIN_SEQ  # AIFI stride-32 tokens
    assert 300 < FLASH_ATTN_MIN_SEQ  # decoder queries


@pytest.mark.tpu
def test_flash_matches_naive_on_tpu():
    """Flash and naive self-attention agree on hardware (incl. the padded
    tail: 1100 tokens pad to 1536 in the kernel, segment ids isolate them)."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs a TPU backend")
    seq = FLASH_ATTN_MIN_SEQ + 76  # non-multiple of the flash block
    flash = np.asarray(_mha_outputs(seq, backend_force_naive=False))
    naive = np.asarray(_mha_outputs(seq, backend_force_naive=True))
    np.testing.assert_allclose(flash, naive, atol=2e-5, rtol=2e-5)


def test_splash_interpret_matches_naive_on_cpu():
    """The splash backend's padding / segment-id / block-size plumbing runs
    on CPU via interpret mode (the msda-ops pattern), so a regression there
    surfaces in CI rather than only on hardware. 1100 tokens pads to 1536:
    a non-multiple of every block size, exercising the pad isolation.
    head_dim is 128 because the current jax splash kernel requires
    head_dim % NUM_LANES (128) == 0 — smaller heads (the original 8 here)
    raise NotImplementedError before the plumbing under test even runs."""
    from spotter_tpu.models.layers import _splash_self_attention

    rng = np.random.default_rng(0)
    b, s, h, hd = 1, 1100, 2, 128
    scale = hd**-0.5
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32) * scale
    k = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)

    got = _splash_self_attention(q, k, v, interpret=True)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    weights = jax.nn.softmax(logits, axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


def test_splash_block_kv_policy():
    """The swept block_kv ladder (pre-round note, round 3-4, git history): 2304 when it
    divides the padded length (yolos 4608), full-row kv up to 3840
    (owlv2's 3601->3840: 10.18 vs 12.67 ms/layer at the old 768
    fallback), else the 768-multiple fallback."""
    from spotter_tpu.models.layers import _splash_block_kv

    assert _splash_block_kv(4608) == 2304
    assert _splash_block_kv(2304) == 2304
    assert _splash_block_kv(3840) == 3840  # owlv2: full-row kv
    assert _splash_block_kv(3072) == 3072
    assert _splash_block_kv(1536) == 1536
    assert _splash_block_kv(768) == 768
    assert _splash_block_kv(6144) == 1536  # >3840, not 2304-divisible
    assert _splash_block_kv(5376) == 768


def test_splash_block_q_policy():
    """Round-5 bq sweep: 512 at the >=4608 shapes it divides (yolos 4608:
    12.0 vs 13.6 ms/layer-attn), 384 elsewhere (3840 cannot take 512 —
    block_q must divide s_pad — and smaller shapes were swept at 384)."""
    from spotter_tpu.models.layers import _splash_block_q

    assert _splash_block_q(4608) == 512
    assert _splash_block_q(5120) == 512
    assert _splash_block_q(3840) == 384  # 512 does not divide
    assert _splash_block_q(3072) == 384  # below the measured 4608 scope
    assert _splash_block_q(768) == 384
    assert _splash_block_q(384) == 384
    assert _splash_block_q(4992) == 384  # >=4608 but 512 does not divide
