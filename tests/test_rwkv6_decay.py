"""RWKV-6 is served to the precision of its float32 reference.

The time mix hands every WKV implementation its log-decay lw = -exp(decay +
dd) in float32.  Near w = 1, the channels that carry the recurrence's long
memory, bfloat16 steps by 2^-8 and rounds every w above 0.998 to 1: no decay
at all.  And rwkv6-1.6b runs its activations in float32 over bfloat16
weights (``ArchConfig.act_dtype``): through a deep stack every bfloat16
rounding of an activation grows into a visible share of the logits.  These
tests run the program at the reduced config on the CPU against the plain
float32 reference of the benchmark (``chipbench/reference/rwkv6.py``), and
plant the old bfloat16 cast of w, and bfloat16 activations, back to show
that the comparison sees them.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run as bench
from chipbench.reference import base
from chipbench.reference import rwkv6 as reference
from repro.configs import ARCHS
from repro.kernels import pallas_wkv6, ref
from repro.models import build_model
from repro.models.ssm import rwkv6_time_mix, wkv6_chunked, wkv6_sequential
from repro.models.transformer import Variants
from repro.serving import ServingEngine

CFG = dataclasses.replace(ARCHS["rwkv6-1.6b"].reduced(), dtype="bfloat16")
M = bench.model_numbers(CFG)


def bf16_decay(impl):
    """``impl`` handed w rounded to bfloat16, as the time mix once did."""
    def wkv(r, k, v, lw, u, state=None):
        w = jnp.exp(lw).astype(jnp.bfloat16).astype(jnp.float32)
        return impl(r, k, v, jnp.log(w), u, state)
    return wkv


def rel_to_largest(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


# The time mix alone over 512 tokens, in bfloat16, against the float32
# reference.  Tolerance 0.03 of the reference's largest output: bfloat16
# keeps 8 bits, so each rounding of the inputs, weights, r, k, v and the
# WKV output is worth about 2^-9 relative, and the sound program reads
# 0.008-0.009 on three seeds.  With w cast to bfloat16 the channels above
# 0.998 stop decaying and it reads 0.13-0.17.
TIME_MIX_TOL = 0.03


@pytest.mark.parametrize("impl,sound", [(wkv6_chunked, True),
                                        (bf16_decay(wkv6_chunked), False)],
                         ids=["float32_decay", "bf16_decay_planted"])
def test_time_mix_over_a_long_sequence(impl, sound):
    seq = 512
    w = base.make_weights(reference.param_tree(M), 2**31 + 21, "bfloat16")
    p = base.layer(w["blocks"], 0)
    # the reference's decay ramp (-6 to -1 in layer 0), moved down by 2 so
    # that about a fifth of the channels have w = exp(-exp(decay)) above
    # 0.998; rounded to the program's bfloat16 for both sides
    p["tm"]["decay"] = (p["tm"]["decay"] - 2.0).astype(jnp.bfloat16) \
        .astype(jnp.float32)
    slow = np.exp(-np.exp(np.asarray(p["tm"]["decay"]))) > 0.998
    assert 0.1 < slow.mean() < 0.5
    # with the channel mix's output weights zeroed, the reference's layer
    # adds only its time mix to x
    p["cm"]["wv"] = jnp.zeros_like(p["cm"]["wv"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, seq, M["d_model"]))
    with jax.default_matmul_precision("highest"):
        want = reference._layer(p, x, M, False) - x
    h = base.layer_norm(x, p["norm1"]["w"], p["norm1"]["b"])
    tm = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p["tm"])
    got, state = rwkv6_time_mix(tm, h.astype(jnp.bfloat16), CFG, None, impl)
    assert got.dtype == jnp.bfloat16 and state["wkv"].dtype == jnp.float32
    err = rel_to_largest(got, want)
    assert (err <= TIME_MIX_TOL) is sound, err


@pytest.mark.parametrize("s", [1, 96])
def test_implementations_agree_on_the_log_decay(s):
    """The WKV implementations the catalog selects agree with the exact
    recurrence on bfloat16 r, k, v and a float32 log-decay whose slowest
    channels lie above w = 0.998.  At 96 tokens the chunked form runs three
    chunks and the Pallas wrapper pads a tail of 32 (lw = 0); one token is
    a decode step, padded to 16."""
    b, h, K = 2, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    r, k, v = (jax.random.normal(kk, (b, h, s, K)).astype(jnp.bfloat16)
               for kk in ks[:3])
    decay = jax.random.uniform(ks[3], (b, h, s, K), minval=-8.0, maxval=-1.0)
    lw = -jnp.exp(decay)
    assert lw.dtype == jnp.float32 and float(lw.max()) > np.log(0.998)
    u = jax.random.normal(ks[4], (h, K)) * 0.5
    s0 = jax.random.normal(ks[5], (b, h, K, K))
    want_y, want_s = ref.wkv6_ref(r, k, v, lw, u, s0)
    impls = {"sequential": wkv6_sequential,
             "chunked": functools.partial(wkv6_chunked, chunk=32),
             "pallas-interpret": functools.partial(pallas_wkv6,
                                                   interpret=True)}
    for name, impl in impls.items():
        y, st = impl(r, k, v, lw, u, s0)
        assert y.shape == want_y.shape and y.dtype == jnp.bfloat16, name
        # y is rounded to bfloat16 (2^-8 relative); the state stays float32
        assert rel_to_largest(y, want_y) < 1e-2, name
        assert rel_to_largest(st, want_s) < 1e-4, name


# Prefill then decode through the engine, bfloat16 weights and float32
# activations as the cell serves them, against the float32 reference's full
# forward pass over the prompt and the served tokens.  At each position the
# program's logits lie within 1e-3 standard deviations of the reference's
# logits there: the two compute the same float32 arithmetic in another
# order and read 4e-6 to 6e-6 (bfloat16 activations read 0.09-0.10 through
# these two layers, and 0.26-0.28 with w cast to bfloat16 as well).
LOGIT_TOL = 1e-3


@pytest.mark.parametrize("seed", [2**31 + 31, 2**31 + 32])
@pytest.mark.parametrize("impl", [
    wkv6_chunked, functools.partial(pallas_wkv6, interpret=True)],
    ids=["chunked-lax", "pallas-interpret"])
def test_prefill_then_decode_matches_the_reference(impl, seed):
    """Served as the cell serves: exact-length prefill, then one-token
    decode steps; the Pallas wrapper pads the prompt's 40 tokens to 48 and
    each decode token to a chunk of 16."""
    model = build_model(CFG, Variants(wkv_impl=impl))
    w = base.make_weights(reference.param_tree(M), seed, "bfloat16")
    engine = ServingEngine(model, w, num_slots=2, max_seq=256,
                           prefill_buckets=(64,))
    # the token-shift states keep the activations' float32 between steps
    assert {a.dtype for a in jax.tree.leaves(engine.cache)} == {
        jnp.dtype(jnp.float32)}
    decoded = []
    step = model.decode_step

    def recorded(*args, **kw):
        logits, cache = step(*args, **kw)
        jax.debug.callback(lambda l: decoded.append(np.asarray(l[0])),
                           logits, ordered=True)
        return logits, cache
    model.decode_step = recorded

    prompt = np.random.default_rng(seed).integers(1, CFG.vocab, 40)
    n_new = 80
    first, _, _ = engine.prefill(prompt)
    engine.submit(prompt.tolist(), max_new_tokens=n_new)
    (resp,) = engine.run_until_drained()
    assert len(resp.tokens) == n_new and len(decoded) == n_new - 1
    got = np.concatenate([np.asarray(first, np.float32),
                          np.asarray(decoded, np.float32)])

    seq = np.concatenate([prompt, resp.tokens[:-1]])
    hid = reference.hidden(w, jnp.asarray(seq[None]), M)[0]
    want = np.asarray(base.einsum("sd,dv->sv", hid, reference.head(w, M))
                      )[len(prompt) - 1:]
    dev = np.abs(got - want).max(-1) / want.std(-1)
    assert dev.max() <= LOGIT_TOL, (dev.argmax(), dev.max())


# Twelve layers at the reduced widths, bfloat16 weights, the program's full
# forward pass against the reference's over 96 tokens: the widest logit
# deviation in standard deviations of the reference's logits.  Float32
# activations read 1e-4 at most on four seeds; bfloat16 activations read
# 0.44-1.39, each rounding of 2^-9 grown through the layers' products.
DEEP_TOL = 0.05


@pytest.mark.parametrize("act_dtype,sound", [("float32", True), ("", False)],
                         ids=["float32_activations",
                              "bf16_activations_planted"])
def test_activations_hold_a_deep_stack_to_the_reference(act_dtype, sound):
    cfg = dataclasses.replace(CFG, num_layers=12, act_dtype=act_dtype)
    m = bench.model_numbers(cfg)
    w = base.make_weights(reference.param_tree(m), 2**31 + 43, "bfloat16")
    model = build_model(cfg, Variants())
    toks = jnp.asarray(np.random.default_rng(3).integers(1, cfg.vocab,
                                                          (1, 96)))
    e = model.embed(w, {"tokens": toks})
    assert e.dtype == jnp.dtype(act_dtype or "bfloat16")
    got = model.logits_fn(w, e, jnp.arange(96)[None])[0][0]
    want = base.einsum("sd,dv->sv", reference.hidden(w, toks, m)[0],
                       reference.head(w, m))
    dev = float((jnp.abs(got - want).max(-1) / want.std(-1)).max())
    assert (dev <= DEEP_TOL) is sound, dev
