"""repro.obs, the program's own spans, and what the serving engine records
with them: spans off and on, the engine's tokens either way, its counters
against hand counts, and where its warm-up compiles land."""
import jax
import pytest

from repro import obs
from repro.configs import get_config
from repro.models import init_params, model_defs
from repro.serve import ServeEngine

ADMIT = {"serve.prefill", "serve.splice", "serve.first_token"}
STEP = {"serve.decode", "serve.pin", "serve.fetch", "serve.sample",
        "serve.retire"}
# the third prompt is longer than max_seq - max_new - 1 and is cut to 18
PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], list(range(1, 31)), [9, 10]]
MAX_SEQ, MAX_NEW = 24, 5


@pytest.fixture(autouse=True)
def tracing_off():
    obs.enable(False)
    obs.reset()
    yield
    obs.enable(False)
    obs.reset()


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tacc-100m", smoke=True)
    return cfg, init_params(model_defs(cfg), jax.random.PRNGKey(0))


@pytest.fixture
def annotations(monkeypatch):
    """Every ``jax.profiler.TraceAnnotation`` made, by name."""
    made = []
    real = jax.profiler.TraceAnnotation

    def note(name, **kw):
        made.append(name)
        return real(name, **kw)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", note)
    return made


def test_off_records_nothing_and_returns_the_shared_noop(annotations):
    a, b = obs.span("serve.step", step=3), obs.span("serve.admit")
    assert a is b
    with a:
        with obs.span("serve.decode"):
            pass
    assert obs.spans() == [] and annotations == []


def test_on_records_nested_spans_with_parents_and_ids(annotations):
    obs.enable(True)
    with obs.span("outer", step=3):
        with obs.span("inner"):
            pass
        with obs.span("inner", request=7):
            pass
    got = obs.spans()
    assert [(s.name, s.parent, s.ids) for s in got] == [
        ("inner", "outer", {}), ("inner", "outer", {"request": 7}),
        ("outer", None, {"step": 3})]
    outer = got[-1]
    assert all(outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns
               for s in got)
    assert got[0].end_ns <= got[1].start_ns
    assert annotations == ["outer", "inner", "inner"]
    obs.reset()
    assert obs.spans() == []


def _serve(model, traced):
    cfg, params = model
    engine = ServeEngine(cfg, params, max_batch=2, max_seq=MAX_SEQ)
    obs.enable(traced)
    results = engine.run(PROMPTS, max_new=MAX_NEW)
    obs.enable(False)
    return engine, [r.tokens for r in results]


def test_engine_serves_identical_tokens_with_tracing_on_and_off(
        model, annotations):
    off_engine, off = _serve(model, False)
    assert annotations == [] and obs.spans() == []
    on_engine, on = _serve(model, True)
    assert on == off
    assert on_engine.counters == off_engine.counters
    spans = obs.spans()
    assert annotations and len(annotations) == len(spans)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["serve.admit"]) == len(PROMPTS)
    assert len(by_name["serve.step"]) == on_engine.counters["decode_steps"]
    assert [s.ids for s in by_name["serve.admit"]] == [
        {"request": i} for i in range(len(PROMPTS))]
    assert [s.ids for s in by_name["serve.step"]] == [
        {"step": i} for i in range(on_engine.counters["decode_steps"])]
    for s in spans:
        want = ("serve.admit" if s.name in ADMIT else
                "serve.step" if s.name in STEP else None)
        assert s.parent == want, s


def test_engine_counters_equal_hand_counts(model):
    cfg, params = model
    engine = ServeEngine(cfg, params, max_batch=2, max_seq=MAX_SEQ)
    live, queue = [], list(PROMPTS)
    hand = dict.fromkeys(engine.counters, 0)
    while queue or live:
        while queue and engine.active() < engine.max_batch:
            p = queue.pop(0)
            live.append(engine.add_request(p, max_new=MAX_NEW))
            hand["admitted"] += 1
            hand["prefill_tokens"] += min(len(p), MAX_SEQ - MAX_NEW - 1)
            hand["prefill_padded_tokens"] += MAX_SEQ
        hand["decode_steps"] += 1
        hand["decode_rows"] += len(live)
        hand["decode_kv_tokens"] += sum(len(g.prompt) + len(g.tokens)
                                        for g in live)
        engine.step()
        live = [g for g in live if not g.done]
    assert engine.counters == hand
    assert hand["prefill_tokens"] == 3 + 5 + 18 + 2


def test_warm_up_compile_lands_under_prefill_or_decode(model):
    cfg, params = model
    engine = ServeEngine(cfg, params, max_batch=2, max_seq=MAX_SEQ)
    obs.reset()                    # building the cache may compile too
    obs.enable(True)
    engine.run([[1] * 8], max_new=2)
    got = obs.compiles()
    assert got.get("serve.prefill", 0) >= 1, got
    assert got.get("serve.decode", 0) >= 1, got
    assert set(got) <= ADMIT | STEP, got
