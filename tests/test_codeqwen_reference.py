"""CodeQwen1.5-7B against its plain reference at reduced size on the CPU.

The benchmark's reference (``chipbench/reference/codeqwen.py``) draws the q,
k and v biases, so a program that drops them, or adds them to the wrong
heads, is told apart from one that serves them.  These tests check that the
reference's weights are the program's tree, that the biases are drawn, that
the engine's prefill followed by decode steps through its cache gives the
reference's full-forward logits (on one device and on a (1, 4) mesh of four
host devices, where each device holds 8 query heads and 1 kv head, as each
chip does at full width), and that the benchmark's check fails a program
whose biases are zeroed.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench import run as bench
from chipbench.reference import base
from chipbench.reference import codeqwen as reference
from chipbench.traffic import Req
from repro.configs import ARCHS
from repro.models import build_model

ROOT = Path(__file__).resolve().parents[1]
# the reduced config with full width's heads per kv head: 32 query heads
# over 4 kv heads, so a (1, 4) mesh leaves 8 and 1 on each device
CFG = dataclasses.replace(ARCHS["codeqwen1.5-7b"].reduced(), n_heads=32,
                          n_kv=4)
M = bench.model_numbers(CFG)
SEED = 2**31 + 1919
# float32 program and reference, both at the highest matmul precision: what
# is left is the order of the sums (the model's blocked attention, the
# engine's padded prefill), a few 1e-6 of the logits' scale
LOGIT_TOL = 2e-4
REHEARSE_LIMIT = json.loads(
    (ROOT / "chipbench/configs/codeqwen1.5-7b.json").read_text()
)["check"]["rehearse_limits"]["widest_logit_gap"]


def weights(seed=SEED):
    return base.make_weights(reference.param_tree(M), seed, CFG.dtype)


def test_weights_fit_the_program():
    got = jax.eval_shape(weights)
    want = build_model(CFG).param_shapes()
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)


def test_published_parameter_count():
    """32 query heads over 4 kv heads give the published 7.25 B parameters
    (as many kv heads as query heads would give 8.19 B)."""
    model = build_model(ARCHS["codeqwen1.5-7b"])
    n = sum(int(np.prod(x.shape))
            for x in jax.tree.leaves(model.param_shapes()))
    assert n == pytest.approx(7.25e9, rel=0.01)
    assert ARCHS["codeqwen1.5-7b"].param_count() == pytest.approx(n, rel=1e-3)


def test_biases_are_drawn():
    """q, k and v biases are N(0, 0.02^2), one draw per layer and bias, and
    the published model's widths are kept: 32 layers of 4096 + 2 x 512."""
    attn = weights()["blocks"]["attn"]
    for name in ("bq", "bk", "bv"):
        b = np.asarray(attn[name])
        assert np.std(b) == pytest.approx(0.02, rel=0.15), name
        assert np.count_nonzero(b) == b.size
        assert not np.allclose(b[0], b[1])            # per layer
    full = reference.param_tree(bench.model_numbers(ARCHS["codeqwen1.5-7b"]))
    assert full["blocks"]["attn"]["bq"][0] == (32, 4096)
    assert full["blocks"]["attn"]["bk"][0] == (32, 512)
    assert full["blocks"]["attn"]["bk"][1] == "small"


def _build(mesh_shape, mesh_axes):
    from repro.core import LazyBuilder, catalog
    from repro.launch.serve import build_serving
    inst = build_serving(LazyBuilder(catalog.build_service()), CFG,
                         mesh_shape, mesh_axes)
    inst.wait("weights")
    return inst


def engine_logits(inst, params, prompt, n_steps):
    """The engine's prefill of ``prompt`` (padded to its bucket), then
    ``n_steps`` greedy decode steps through the prefilled cache: the logits
    of every step, (n_steps + 1, vocab), and the tokens fed."""
    engine = inst.entry["make_engine"](params, num_slots=1, max_seq=32,
                                       prefill_buckets=(16,))
    logits, cache, n = engine.prefill(prompt)
    out, fed = [np.asarray(logits[0])], []
    for t in range(n_steps):
        tok = int(np.argmax(out[-1]))
        fed.append(tok)
        pos = jnp.asarray([[n + t]], jnp.int32)
        lg, cache = inst.entry["decode_step"](
            params, jnp.asarray([[tok]], jnp.int32), pos, cache,
            jnp.asarray([n + t], jnp.int32))
        out.append(np.asarray(lg[0]))
    return np.stack(out), fed


def reference_logits(w, prompt, fed):
    """The reference over prompt + fed tokens, at the positions the engine
    produced its logits."""
    toks = jnp.asarray([list(prompt) + fed], jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = reference.hidden(w, toks, M)
    got = base.einsum("bsd,dv->bsv", h, reference.head(w, M))[0]
    return np.asarray(got[len(prompt) - 1:])


def _prompt():
    return np.random.default_rng(5).integers(1, CFG.vocab, 11).tolist()


def test_engine_through_its_cache_matches_the_reference():
    inst = _build((1,), ("data",))
    w = weights()
    with jax.default_matmul_precision("highest"):
        got, fed = engine_logits(inst, w, _prompt(), 6)
    want = reference_logits(w, _prompt(), fed)
    np.testing.assert_allclose(got, want, rtol=LOGIT_TOL, atol=LOGIT_TOL)


_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path[:0] = [{root!r}, {src!r}]
    import json
    import jax
    import numpy as np
    from tests import test_codeqwen_reference as t

    inst = t._build((1, 4), ("data", "model"))
    w = t.weights()
    params = jax.device_put(w, inst.entry["param_shardings"]())
    engine = inst.entry["make_engine"](params, num_slots=2, max_seq=32,
                                       prefill_buckets=(16,))
    k = engine.cache["blocks"]["k"]
    with jax.default_matmul_precision("highest"):
        got, fed = t.engine_logits(inst, params, t._prompt(), 6)
    want = t.reference_logits(w, t._prompt(), fed)
    print(json.dumps({{
        "err": float(np.abs(got - want).max()),
        "scale": float(np.abs(want).max()),
        "cache_spec": [str(a) for a in k.sharding.spec],
        "kv_heads_per_device": k.addressable_shards[0].data.shape[2],
        "wq_cols_per_device":
            params["blocks"]["attn"]["wq"].addressable_shards[0].data.shape[-1],
    }}))
""")


def test_engine_on_four_devices_matches_the_reference():
    """On a (1, 4) mesh: each device holds one kv head of the cache and 8
    query heads' columns of wq, and the logits are the reference's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    script = _SHARDED.format(root=str(ROOT), src=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["err"] <= LOGIT_TOL * max(1.0, got["scale"]), got
    assert got["kv_heads_per_device"] == 1, got
    assert got["wq_cols_per_device"] == 8 * CFG.head_dim, got


def _served(params, prompts, n_new):
    """Greedy tokens the engine serves for ``prompts``, as the harness's
    finished requests."""
    inst = _build((1,), ("data",))
    engine = inst.entry["make_engine"](params, num_slots=2, max_seq=64,
                                       prefill_buckets=(16, 32))
    for p in prompts:
        engine.submit(p, max_new_tokens=n_new)
    got = {r.rid: r.tokens for r in engine.run_until_drained()}
    return [Req(idx=i, prompt=np.asarray(p, np.int32), max_new=n_new,
                due=0.0, tokens=got[i]) for i, p in enumerate(prompts)]


@pytest.mark.parametrize("zero_biases", [False, True],
                         ids=["served", "biases_zeroed"])
def test_check_sees_the_biases(zero_biases):
    """The benchmark's check at its rehearsal limit: the program served the
    reference's weights passes it, the same program with its q, k and v
    biases zeroed fails it."""
    w = weights()
    if zero_biases:
        attn = w["blocks"]["attn"]
        for name in ("bq", "bk", "bv"):
            attn[name] = jnp.zeros_like(attn[name])
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, CFG.vocab, n).tolist() for n in (9, 20, 30)]
    sample = _served(w, prompts, 12)
    gaps = check.gaps("codeqwen", M, SEED, sample, 64)
    widest = check.numbers(gaps["served"])["widest_logit_gap"]
    if zero_biases:
        assert widest > REHEARSE_LIMIT
    else:
        assert widest <= REHEARSE_LIMIT
