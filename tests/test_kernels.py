"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles,
plus the lax variants vs the same oracles and decode-path equivalences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import pallas_attention, pallas_wkv6, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.rwkv6_scan import wkv6_pallas, wkv6_step_pallas
from repro.models.attention import lax_flash_attention, naive_attention
from repro.models.ssm import wkv6_chunked, wkv6_sequential


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 4, 4, 128, 64),        # MHA
    (2, 8, 2, 256, 64),        # GQA 4:1
    (1, 6, 1, 128, 32),        # MQA
    (1, 4, 2, 512, 128),       # long-ish, MXU-aligned head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(b, hq, hkv, s, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, scale=d ** -0.5,
                          block_q=64, block_k=64,
                          interpret=True)
    exp = ref.attention_ref(q, k, v, scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window,softcap", [(64, 0.0), (0, 30.0), (32, 50.0)])
def test_flash_attention_window_softcap(window, softcap):
    b, hq, hkv, s, d = 1, 4, 2, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    out = flash_attention(q, k, v, scale=0.2, window=window, softcap=softcap,
                          block_q=64, block_k=64,
                          interpret=True)
    exp = ref.attention_ref(q, k, v, scale=0.2, window=window,
                            softcap=softcap)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=3e-5)


def test_flash_attention_mla_asymmetric_vdim():
    """MLA: qk head dim 192, v head dim 128."""
    b, h, s = 1, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, h, s, 192))
    k = jax.random.normal(ks[1], (b, h, s, 192))
    v = jax.random.normal(ks[2], (b, h, s, 128))
    out = flash_attention(q, k, v, scale=192 ** -0.5,
                          block_q=64, block_k=64,
                          interpret=True)
    exp = ref.attention_ref(q, k, v, scale=192 ** -0.5)
    assert out.shape == (b, h, s, 128)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=3e-5)


def test_pallas_attention_unaligned_length_raises_when_compiled():
    """A length the flash blocks do not tile raises on the compiled path
    instead of quietly running another kernel; interpret mode still
    answers (through the naive oracle)."""
    b, h, s, d = 1, 2, 80, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)
    with pytest.raises(ValueError, match="multiples of its blocks"):
        pallas_attention(q, k, v, scale=0.3, block_q=64, block_k=64,
                         interpret=False)
    out = pallas_attention(q, k, v, scale=0.3, block_q=64, block_k=64,
                           interpret=True)
    np.testing.assert_allclose(out, ref.attention_ref(q, k, v, scale=0.3),
                               atol=3e-5, rtol=3e-5)


def test_lax_flash_matches_ref_and_naive():
    b, hq, hkv, s, d = 2, 4, 2, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    out = lax_flash_attention(q, k, v, scale=0.3, block_q=64, block_k=64)
    np.testing.assert_allclose(out, ref.attention_ref(q, k, v, scale=0.3),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(
        out, naive_attention(q, k, v, scale=0.3), atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------

def _log_decay(key, shape):
    """log w in float32 for w in (0.05, 0.95)."""
    w = jax.nn.sigmoid(jax.random.normal(key, shape)) * 0.9 + 0.05
    return jnp.log(w)


@pytest.mark.parametrize("b,h,s,K,chunk", [
    (1, 2, 128, 32, 32),
    (2, 3, 64, 16, 16),
    (1, 1, 256, 64, 64),
])
def test_wkv6_pallas_vs_ref(b, h, s, K, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    r = jax.random.normal(ks[0], (b, h, s, K))
    k = jax.random.normal(ks[1], (b, h, s, K))
    v = jax.random.normal(ks[2], (b, h, s, K))
    lw = _log_decay(ks[3], (b, h, s, K))
    u = jax.random.normal(ks[4], (h, K)) * 0.1
    y, S = wkv6_pallas(r, k, v, lw, u, chunk=chunk, interpret=True)
    ye, Se = ref.wkv6_ref(r, k, v, lw, u)
    np.testing.assert_allclose(y, ye, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(S, Se, atol=2e-4, rtol=2e-4)


def test_wkv6_pallas_with_initial_state():
    b, h, s, K = 1, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    r = jax.random.normal(ks[0], (b, h, s, K))
    k = jax.random.normal(ks[1], (b, h, s, K))
    v = jax.random.normal(ks[2], (b, h, s, K))
    lw = _log_decay(ks[3], (b, h, s, K))
    u = jax.random.normal(ks[4], (h, K)) * 0.1
    s0 = jax.random.normal(ks[5], (b, h, K, K), jnp.float32)
    y, S = wkv6_pallas(r, k, v, lw, u, s0, chunk=32, interpret=True)
    ye, Se = ref.wkv6_ref(r, k, v, lw, u, s0)
    np.testing.assert_allclose(y, ye, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(S, Se, atol=2e-4, rtol=2e-4)


def _wkv6_inputs(seed, b, h, s, K, dtype=jnp.float32):
    """r, k, v in ``dtype``, log-decay, bonus u and a random f32 state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r, k, v = (jax.random.normal(ks[i], (b, h, s, K)).astype(dtype)
               for i in range(3))
    lw = _log_decay(ks[3], (b, h, s, K))
    u = jax.random.normal(ks[4], (h, K)) * 0.1
    s0 = jax.random.normal(ks[5], (b, h, K, K), jnp.float32)
    return r, k, v, lw, u, s0


@pytest.mark.parametrize("b,h,K,dtype", [
    (2, 3, 16, jnp.float32),
    (16, 32, 64, jnp.float32),      # rwkv6-1.6b decode over 16 slots
    (2, 4, 64, jnp.bfloat16),
], ids=["small", "rwkv6-decode", "bf16"])
def test_wkv6_step_vs_ref(b, h, K, dtype):
    """One token from a random state: the step kernel against the
    sequential recurrence."""
    args = _wkv6_inputs(10, b, h, 1, K, dtype)
    y, S = wkv6_step_pallas(*args, interpret=True)
    ye, Se = ref.wkv6_ref(*args)
    assert y.shape == (b, h, 1, K) and y.dtype == dtype
    np.testing.assert_allclose(y.astype(jnp.float32), ye.astype(jnp.float32),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(S, Se, atol=2e-4, rtol=2e-4)


def test_wkv6_step_chained_matches_ref_over_64_tokens():
    """64 single-token calls, each handed the state the last returned, give
    the recurrence over the same 64 tokens."""
    r, k, v, lw, u, s0 = _wkv6_inputs(11, 2, 3, 64, 16)
    S, ys = s0, []
    for t in range(64):
        tok = lambda x: x[:, :, t:t + 1]
        y, S = wkv6_step_pallas(tok(r), tok(k), tok(v), tok(lw), u, S,
                                interpret=True)
        ys.append(y)
    ye, Se = ref.wkv6_ref(r, k, v, lw, u, s0)
    np.testing.assert_allclose(jnp.concatenate(ys, axis=2), ye,
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(S, Se, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("s", [1, 50, 100])
def test_pallas_wkv6_pads_any_length_exactly(s):
    """One token runs the step kernel; longer inputs are padded at the tail
    (r = k = v = 0, lw = 0) instead of falling back: output and carried
    state match the recurrence at any length."""
    b, h, K = 1, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    r = jax.random.normal(ks[0], (b, h, s, K))
    k = jax.random.normal(ks[1], (b, h, s, K))
    v = jax.random.normal(ks[2], (b, h, s, K))
    lw = _log_decay(ks[3], (b, h, s, K))
    u = jax.random.normal(ks[4], (h, K)) * 0.1
    s0 = jax.random.normal(ks[5], (b, h, K, K), jnp.float32)
    y, S = pallas_wkv6(r, k, v, lw, u, s0, interpret=True)
    ye, Se = wkv6_sequential(r, k, v, lw, u, s0)
    assert y.shape == (b, h, s, K)
    np.testing.assert_allclose(y, ye, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(S, Se, atol=2e-4, rtol=2e-4)


def test_wkv6_chunked_and_sequential_match_ref():
    b, h, s, K = 2, 2, 96, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    r = jax.random.normal(ks[0], (b, h, s, K))
    k = jax.random.normal(ks[1], (b, h, s, K))
    v = jax.random.normal(ks[2], (b, h, s, K))
    lw = _log_decay(ks[3], (b, h, s, K))
    u = jax.random.normal(ks[4], (h, K)) * 0.1
    ye, _ = ref.wkv6_ref(r, k, v, lw, u)
    y1, _ = wkv6_sequential(r, k, v, lw, u)
    y2, _ = wkv6_chunked(r, k, v, lw, u, chunk=32)
    np.testing.assert_allclose(y1, ye, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(y2, ye, atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 128), (2, 37, 512), (5, 3, 7, 64)])
@pytest.mark.parametrize("plus_one", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas_vs_ref(shape, plus_one, dtype):
    x = jax.random.normal(jax.random.PRNGKey(7), shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(8), (shape[-1],), dtype)
    out = rmsnorm_pallas(x, w, plus_one=plus_one, block_rows=16,
                         interpret=True)
    exp = ref.rmsnorm_ref(x, w, plus_one=plus_one)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# decode-path equivalences (cache vs full forward)
# ---------------------------------------------------------------------------

def test_gqa_decode_matches_train_attention():
    """Prefill+decode through the KV cache reproduces the full causal
    attention output for the decoded position."""
    from repro.configs import ARCHS
    from repro.models.attention import gqa_attention, gqa_cache_spec
    from repro.models.common import init_tree
    from repro.models.attention import gqa_spec
    import dataclasses
    cfg = dataclasses.replace(ARCHS["starcoder2-3b"].reduced(), qkv_bias=False)
    p = init_tree(jax.random.PRNGKey(0), gqa_spec(cfg))
    b, s = 2, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s + 1, cfg.d_model))
    pos = jnp.tile(jnp.arange(s + 1), (b, 1))
    full, _ = gqa_attention(p, x, cfg, positions=pos, kernel=naive_attention)

    cache = init_tree(jax.random.PRNGKey(2),
                      gqa_cache_spec(cfg, b, 32))
    cache = jax.tree.map(jnp.zeros_like, cache)
    _, cache = gqa_attention(p, x[:, :s], cfg, positions=pos[:, :s],
                             kernel=naive_attention, cache=cache, cache_pos=0)
    out1, _ = gqa_attention(p, x[:, s:], cfg, positions=pos[:, s:],
                            kernel=naive_attention, cache=cache, cache_pos=s)
    np.testing.assert_allclose(out1[:, 0], full[:, s], atol=1e-4, rtol=1e-4)


def test_ring_buffer_window_decode_matches_full_cache():
    """Sliding-window ring cache (len=window) decode == full cache decode
    with window masking."""
    from repro.configs import ARCHS
    from repro.models.attention import (gqa_attention, gqa_cache_spec,
                                        write_token)
    from repro.models.common import init_tree
    from repro.models.attention import gqa_spec
    import dataclasses
    cfg = dataclasses.replace(ARCHS["gemma2-9b"].reduced(),
                              attn_softcap=0.0, post_norms=False)
    W = cfg.sliding_window            # 64 in the reduced config
    p = init_tree(jax.random.PRNGKey(0), gqa_spec(cfg))
    b, total = 1, 80                  # > window so wraparound is exercised
    x = jax.random.normal(jax.random.PRNGKey(1), (b, total, cfg.d_model)) \
        * 0.3
    pos = jnp.tile(jnp.arange(total), (b, 1))

    full_cache = jax.tree.map(jnp.zeros_like, init_tree(
        jax.random.PRNGKey(2), gqa_cache_spec(cfg, b, total)))
    ring_cache = jax.tree.map(jnp.zeros_like, init_tree(
        jax.random.PRNGKey(2), gqa_cache_spec(cfg, b, W)))

    for t in range(total):
        xt = x[:, t:t + 1]
        pt = pos[:, t:t + 1]
        o_full, kv = gqa_attention(
            p, xt, cfg, positions=pt, kernel=naive_attention, window=W,
            cache=full_cache, cache_pos=t)
        # the full-length cache is only read; the step's token is written
        # by the caller
        full_cache = {"k": write_token(full_cache["k"], kv.k, t),
                      "v": write_token(full_cache["v"], kv.v, t)}
        o_ring, ring_cache = gqa_attention(
            p, xt, cfg, positions=pt, kernel=naive_attention, window=W,
            cache=ring_cache, cache_pos=t)
        np.testing.assert_allclose(o_ring, o_full, atol=1e-4, rtol=1e-4,
                                   err_msg=f"step {t}")


def test_mla_decode_matches_train_path():
    """The compressed-cache (absorbed) MLA decode equals the decompressed
    train attention at the decoded position."""
    from repro.configs import ARCHS
    from repro.models.attention import (mla_attention, mla_cache_spec,
                                        mla_spec)
    from repro.models.common import init_tree
    cfg = ARCHS["deepseek-v3-671b"].reduced()
    p = init_tree(jax.random.PRNGKey(0), mla_spec(cfg))
    b, s = 1, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s + 1, cfg.d_model)) \
        * 0.3
    pos = jnp.tile(jnp.arange(s + 1), (b, 1))
    full, _ = mla_attention(p, x, cfg, positions=pos, kernel=naive_attention)

    cache = jax.tree.map(jnp.zeros_like, init_tree(
        jax.random.PRNGKey(2), mla_cache_spec(cfg, b, 32)))
    _, cache = mla_attention(p, x[:, :s], cfg, positions=pos[:, :s],
                             cache=cache, cache_pos=0)
    out1, _ = mla_attention(p, x[:, s:], cfg, positions=pos[:, s:],
                            cache=cache, cache_pos=s)
    np.testing.assert_allclose(out1[:, 0], full[:, s], atol=2e-4, rtol=2e-4)
