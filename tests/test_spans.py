"""Program spans and compile counts (``repro.spans``) and the serving
engine's per-request stamps."""
import glob
import time

import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.configs import ARCHS
from repro.core import LazyBuilder, PreBuilder, cpu_smoke
from repro.serving import ServingEngine


@pytest.fixture(scope="module")
def model_params(service, smoke_mesh):
    cfg = ARCHS["phi4-mini-3.8b"].reduced()
    inst = LazyBuilder(service).build(
        PreBuilder(service).prebuild(cfg, entrypoint="serve"), cpu_smoke(),
        mesh=smoke_mesh)
    return inst.model, inst.model.init(jax.random.PRNGKey(0))


def _engine(model_params):
    model, params = model_params
    return ServingEngine(model, params, num_slots=2, max_seq=64,
                         prefill_buckets=(16,))


def test_stamps_are_ordered_and_the_parts_add_up(model_params):
    eng = _engine(model_params)
    t_before = time.perf_counter()
    for n in (4, 6, 3):            # three requests, two slots: one queues
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=n)
    reqs = list(eng.queue)
    resp = {r.rid: r for r in eng.run_until_drained()}
    t_after = time.perf_counter()

    finish = {}
    for req in reqs:
        r = resp[req.rid]
        assert t_before <= req.submitted_at <= req.admitted_at \
            <= req.first_token_at == r.first_token_at
        finish[req.rid] = r.first_token_at + r.decode_s
        assert r.first_token_at <= finish[req.rid] <= t_after
        assert r.queued_s == pytest.approx(req.admitted_at - req.submitted_at)
        assert r.prefill_s == pytest.approx(
            req.first_token_at - req.admitted_at)
        assert r.queued_s + r.prefill_s + r.decode_s == pytest.approx(
            finish[req.rid] - req.submitted_at)
    # the third waits in the queue until the first (the shortest) is done
    assert reqs[2].admitted_at >= finish[reqs[0].rid]
    assert resp[reqs[2].rid].queued_s > resp[reqs[0].rid].queued_s


def _host_events(trace_dir):
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.end_ns)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_engine_spans_nest_inside_the_callers_tick(model_params, tmp_path):
    eng = _engine(model_params)
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.run_until_drained()                       # compiles outside the trace
    for n in (4, 3):               # three decode steps in three ticks
        eng.submit([4, 5, 6, 7], max_new_tokens=n)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("tick"):
                eng.tick()
    finally:
        jax.profiler.stop_trace()

    events = _host_events(tmp_path)
    ticks = [(s, e) for n, s, e in events if n == "tick"]
    engine = [(n, s, e) for n, s, e in events if n in spans.NAMES]
    assert len(ticks) == 3
    count = {name: sum(n == name for n, _, _ in engine)
             for name in spans.NAMES}
    assert count == {spans.ADMIT: 2, spans.DECODE: 3, spans.TOKEN_SYNC: 3}
    for n, s, e in engine:
        assert any(ts <= s and e <= te for ts, te in ticks), n


def _fresh(scale):
    return jax.jit(lambda x: x * scale + 1.0)


@pytest.mark.parametrize("open_spans, key", [
    ((), spans.OTHER), (("x",), "x"), (("x", "y"), "y")])
def test_compile_counter_counts_under_the_innermost_span(open_spans, key):
    f, x = _fresh(float(len(open_spans)) + 0.5), jnp.ones(3)
    with spans.CompileCounter() as counter:
        _run_in(open_spans, lambda: f(x).block_until_ready())
    assert set(counter.by_span) == {key}
    kinds = counter.by_span[key]
    assert kinds["lower"][0] == 1 and kinds["compile"][0] == 1
    assert counter.seconds() > 0
    assert counter.by_program["jit(<lambda>)"]["compile"][0] == 1
    assert key in counter.summary()


def test_compile_counter_unregisters_on_exit():
    f, x = _fresh(7.0), jnp.ones(2)
    with spans.CompileCounter() as counter:
        pass
    f(x).block_until_ready()
    assert not counter.by_span and counter.seconds() == 0


def test_span_stack_unwinds_on_error():
    with pytest.raises(RuntimeError):
        with spans.span("a"):
            with spans.span("b"):
                raise RuntimeError
    assert spans._open.names == []


def _run_in(names, fn):
    if not names:
        return fn()
    with spans.span(names[0]):
        return _run_in(names[1:], fn)
