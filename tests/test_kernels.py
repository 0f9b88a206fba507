"""Pallas kernel validation: interpret=True kernel body vs ref.py oracle,
swept over shapes and dtypes; blocked (CPU lowering target) vs oracle; custom
flash VJP vs autodiff-of-oracle gradients.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa, ops, ref

KEY = jax.random.PRNGKey(0)


def _qkv(B, S, H, K, D, dtype):
    """``S`` is one length, or (Sq, Skv)."""
    Sq, Skv = S if isinstance(S, tuple) else (S, S)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, K, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, K, D), jnp.float32).astype(dtype)
    return q, k, v


FLASH_SWEEP = [
    # B, S, H, K, D, causal, window
    (1, 128, 4, 4, 64, True, 0),
    (2, 256, 4, 2, 64, True, 0),        # GQA
    (1, 256, 8, 1, 32, True, 0),        # MQA, small head
    (1, 128, 4, 4, 64, False, 0),       # bidirectional (encoder)
    (1, 256, 4, 2, 64, True, 64),       # sliding window
    (1, 96, 2, 2, 80, True, 0),         # ragged: S % block, D % 128 != 0
    # blocks chosen from the shape, wider than 128
    (1, 1024, 4, 2, 128, True, 0),      # GQA at the train cell's length
    (1, 1000, 4, 2, 128, True, 0),      # ragged above 512: 896 + 104
    (1, 1024, 4, 2, 128, True, 256),    # sliding window
    (1, 2048, 2, 1, 128, True, 256),    # window across blocks: a dead block
    (1, (384, 1024), 4, 2, 128, False, 0),  # Sq < Skv, bidirectional
]


@pytest.mark.parametrize("B,S,H,K,D,causal,window", FLASH_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_pallas_interpret_vs_ref(B, S, H, K, D, causal, window, dtype):
    q, k, v = _qkv(B, S, H, K, D, dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="pallas", interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,S,H,K,D,causal,window", FLASH_SWEEP)
def test_flash_blocked_vs_ref(B, S, H, K, D, causal, window):
    q, k, v = _qkv(B, S, H, K, D, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="blocked", blk_kv=64)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_custom_vjp_matches_autodiff_oracle():
    q, k, v = _qkv(1, 128, 4, 2, 64, jnp.float32)

    def loss_blocked(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, impl="blocked",
                                           blk_kv=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref.attention_ref(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_blocked, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("B,S,H,K,D,causal,window", FLASH_SWEEP)
def test_flash_pallas_vjp_matches_blocked(B, S, H, K, D, causal, window):
    """The Pallas branch's custom VJP (kernel forward, its lse into the
    blocked backward) against ``_flash_blocked``'s gradients."""
    q, k, v = _qkv(B, S, H, K, D, jnp.float32)
    do = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    def grads(impl, **kw):
        _, vjp = jax.vjp(lambda *a: ops.flash_attention(
            *a, causal=causal, window=window, impl=impl, **kw), q, k, v)
        return vjp(do)

    for a, b in zip(grads("pallas", interpret=True), grads("blocked")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


# head-major (B, Sq, Skv, H, K, causal, window, blk_q, blk_kv): blocks given
# explicitly, so that several q and kv blocks meet the masks
FLASH_BLOCKS = [
    (1, 512, 512, 4, 2, True, 0, 128, 128),
    (1, 512, 512, 4, 2, True, 0, 128, 256),
    (1, 512, 512, 4, 2, True, 0, 256, 128),
    (1, 512, 512, 2, 2, True, 128, 128, 128),
    (1, 384, 640, 2, 1, True, 0, 128, 256),     # Sq < Skv, causal
    (1, 384, 384, 2, 1, True, 192, 128, 256),   # window, ragged kv blocks
    (1, 256, 512, 2, 2, False, 0, 128, 128),
]


@pytest.mark.parametrize("B,Sq,Skv,H,K,causal,window,blk_q,blk_kv", FLASH_BLOCKS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_pallas_blocks_vs_ref(B, Sq, Skv, H, K, causal, window, blk_q,
                                    blk_kv, dtype):
    q, k, v = _qkv(B, (Sq, Skv), H, K, 128, dtype)
    hm = lambda t: t.transpose(0, 2, 1, 3)
    o, lse = fa.flash_attention_pallas(hm(q), hm(k), hm(v), causal=causal,
                                       window=window, blk_q=blk_q,
                                       blk_kv=blk_kv, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(hm(o), np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert lse.shape == (B, H, Sq) and lse.dtype == jnp.float32


def test_flash_block_chooser():
    """Blocks follow the shape: whole dims up to 128, multiples of 128 above,
    at most ``BLOCK``, and inside the VMEM budget."""
    assert fa.pick_blocks(1024, 1024, 128, 2) == (1024, 1024)
    assert fa.pick_blocks(5, 5, 128, 2) == (5, 5)
    assert fa.pick_blocks(96, 200, 128, 4) == (96, 128)
    assert fa.pick_blocks(1000, 1000, 128, 2) == (896, 896)
    assert fa.pick_blocks(384, 1024, 128, 2) == (384, 1024)
    # f32 inputs, and a wider head, halve the kv block to fit
    assert fa.pick_blocks(1024, 1024, 128, 4) == (1024, 512)
    assert fa.pick_blocks(8192, 8192, 256, 2) == (1024, 512)
    for sq, skv, d, isz in [(1024, 1024, 128, 2), (8192, 8192, 256, 4),
                            (300, 4096, 512, 4), (130, 130, 128, 2)]:
        bq, bkv = fa.pick_blocks(sq, skv, d, isz)
        for n, b in ((sq, bq), (skv, bkv)):
            assert b <= fa.BLOCK and (b == n <= fa.LANES or b % fa.LANES == 0)
        assert fa._vmem_bytes(bq, bkv, d, isz) <= fa.VMEM_BUDGET


@pytest.mark.parametrize("Sq,Skv,causal,window,blk_q,blk_kv", [
    (1024, 1024, True, 0, 128, 128),
    (1024, 1024, True, 0, 256, 128),
    (1024, 1024, True, 256, 128, 128),
    (1000, 1000, True, 300, 128, 256),
    (512, 2048, True, 0, 128, 512),
    (2048, 2048, False, 0, 512, 512),
])
def test_flash_dead_steps_keep_kv_block(Sq, Skv, causal, window, blk_q, blk_kv):
    """The k/v index map against the mask itself: a step whose blocks share
    no unmasked pair reads the block of the step before it (or, first in its
    row, the first block it will use), so it costs no copy; every other step
    reads its own block."""
    offset = Skv - Sq
    nq, nkv = -(-Sq // blk_q), -(-Skv // blk_kv)
    q_pos = np.arange(Sq)[:, None] + offset
    k_pos = np.arange(Skv)[None, :]
    mask = np.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= q_pos - k_pos < window
    dead_steps = 0
    for i in range(nq):
        idx = [int(fa.kv_block_index(i, j, blk_q=blk_q, blk_kv=blk_kv,
                                     offset=offset, causal=causal,
                                     window=window, num_kv_blocks=nkv))
               for j in range(nkv)]
        live = [mask[i * blk_q:(i + 1) * blk_q,
                     j * blk_kv:(j + 1) * blk_kv].any() for j in range(nkv)]
        for j in range(nkv):
            if live[j]:
                assert idx[j] == j
            elif j > 0:
                dead_steps += 1
                assert idx[j] == idx[j - 1], (i, j, idx)
            else:
                dead_steps += 1
                assert idx[0] == live.index(True), (i, idx)
    if causal and not window and Sq == Skv and blk_q == blk_kv:
        assert dead_steps == nq * (nq - 1) // 2
    if not causal and not window:
        assert dead_steps == 0


SSD_SWEEP = [
    # B, S, H, P, N, chunk
    (1, 128, 2, 32, 16, 32),
    (2, 256, 4, 64, 32, 64),
    (1, 100, 2, 32, 16, 32),            # ragged S % chunk
]


@pytest.mark.parametrize("B,S,H,P,N,chunk", SSD_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas_interpret_vs_ref(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    a = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.2)
    bm = jax.random.normal(ks[3], (B, S, N), jnp.float32).astype(dtype)
    cm = jax.random.normal(ks[4], (B, S, N), jnp.float32).astype(dtype)
    out = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk, impl="pallas",
                       interpret=True)
    want, _ = ref.ssd_ref(x, dt, a, bm, cm)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,S,H,P,N,chunk", SSD_SWEEP)
def test_ssd_blocked_vs_ref_with_state(B, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    a = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.2)
    bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    cm = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    y, h = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk, impl="blocked",
                        return_state=True)
    y_ref, h_ref = ref.ssd_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_decode_step_matches_scan_tail():
    """Running S steps of the decode recurrence == the scan's final state/out."""
    B, S, H, P, N = 1, 32, 2, 16, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    a = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.2)
    bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    cm = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    y_scan, h_scan = ref.ssd_ref(x, dt, a, bm, cm)
    h = jnp.zeros((B, H, N, P), jnp.float32)
    outs = []
    for t in range(S):
        y, h = ops.ssd_decode_step(x[:, t:t+1], dt[:, t:t+1], a,
                                   bm[:, t:t+1], cm[:, t:t+1], h)
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(y_scan), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_scan),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [(2, 64, 128), (1, 7, 256), (4, 1, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas_interpret_vs_ref(shape, dtype):
    x = jax.random.normal(KEY, shape, jnp.float32).astype(dtype)
    sc = jnp.ones((shape[-1],), dtype) * 1.5
    out = ops.rmsnorm(x, sc, impl="pallas", interpret=True)
    want = ref.rmsnorm_ref(x, sc)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_attend_cache_matches_full_attention():
    """Decode attention against a cache == last-row of full causal attention."""
    B, S, H, K, D = 2, 64, 4, 2, 32
    q, k, v = _qkv(B, S, H, K, D, jnp.float32)
    full = ref.attention_ref(q, k, v, causal=True)
    pos = jnp.full((B,), S - 1, jnp.int32)
    out = ops.attend_cache(q[:, -1:], k, v, pos[:, None, None, None])
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-5, atol=1e-5)


def test_attend_cache_packed_matches_reference():
    """§Perf decode lever: packed GQA decode == repeat-based reference."""
    B, S, H, K, D = 2, 64, 8, 2, 32
    q, k, v = _qkv(B, S, H, K, D, jnp.float32)
    pos = jnp.array([S - 1, S // 2])[:, None, None, None]
    a = ops.attend_cache(q[:, -1:], k, v, pos)
    b = ops.attend_cache(q[:, -1:], k, v, pos, packed=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
    # and with a sliding window
    aw = ops.attend_cache(q[:, -1:], k, v, pos, window=16)
    bw = ops.attend_cache(q[:, -1:], k, v, pos, window=16, packed=True)
    np.testing.assert_allclose(np.asarray(aw), np.asarray(bw),
                               rtol=2e-5, atol=2e-5)
