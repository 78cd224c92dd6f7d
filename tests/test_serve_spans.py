"""The serving layer's trace spans: the engine and the router open the
names of ``repro/serve/spans.py`` in the nesting the trace reduction
reads (``bench/program_trace.py``), with the request id and page counts
as metadata, and the tick stays one transfer-free program."""
import jax
import pytest

from repro.models import build_model
from repro.models.config import ModelConfig, ParallelConfig
from repro.serve import BatchedEngine, Request, ServeConfig, make_cells
from repro.serve import spans

PS = 8


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span
    with the names of the spans open around it."""

    open: list = []
    seen: list = []

    def __init__(self, name, **meta):
        self.name, self.meta = name, meta

    def __enter__(self):
        Recorder.seen.append((tuple(s.name for s in Recorder.open),
                              self.name, self.meta))
        Recorder.open.append(self)
        return self

    def __exit__(self, *exc):
        assert Recorder.open.pop() is self
        return False


@pytest.fixture
def recorded(monkeypatch):
    Recorder.open, Recorder.seen = [], []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return Recorder.seen


@pytest.fixture(scope="module")
def model_params():
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256, dtype="float32")
    model = build_model(cfg, ParallelConfig(remat="none"))
    return model, model.init_params(jax.random.PRNGKey(0))


def engine(model_params, paged=True):
    model, params = model_params
    cfg = ServeConfig(batch_slots=2, max_seq_len=32, eos_id=-1,
                      page_size=PS if paged else None,
                      num_pages=8 if paged else None)
    return BatchedEngine(model, params, cfg)


def prompt(n, start=2):
    return list(range(start, start + n))


def test_admission_spans_nest_under_serve_admit(model_params, recorded):
    eng = engine(model_params)
    eng.admit([Request(rid=7, prompt=prompt(12), max_new_tokens=3),
               Request(rid=9, prompt=prompt(5, 40), max_new_tokens=3)])
    A = (spans.ADMIT,)
    assert recorded == [
        ((), spans.ADMIT, {}),
        (A, spans.ADMIT_REAP, {}),
        (A + (spans.ADMIT_REAP,), spans.SYNC, {}),
        (A, spans.ADMIT_PREFILL, {"rid": 7}),
        (A, spans.ADMIT_SCATTER, {"rid": 7, "fresh_pages": 2,
                                  "shared_pages": 0}),
        (A, spans.ADMIT_PREFILL, {"rid": 9}),
        (A, spans.ADMIT_SCATTER, {"rid": 9, "fresh_pages": 1,
                                  "shared_pages": 0}),
        (A, spans.ADMIT_FIRST_TOKENS, {}),
    ]


def test_scatter_span_counts_shared_pages(model_params, recorded):
    eng = engine(model_params)
    eng.admit([Request(rid=1, prompt=prompt(20), max_new_tokens=2),
               Request(rid=2, prompt=prompt(20), max_new_tokens=2)])
    scatters = [m for _, n, m in recorded if n == spans.ADMIT_SCATTER]
    # 20 tokens + 1 decode position: 3 pages, the 2 full ones shareable
    assert scatters == [{"rid": 1, "fresh_pages": 3, "shared_pages": 0},
                        {"rid": 2, "fresh_pages": 1, "shared_pages": 2}]


def test_dense_engine_scatter_span_carries_the_request(model_params,
                                                       recorded):
    eng = engine(model_params, paged=False)
    eng.admit([Request(rid=4, prompt=prompt(6), max_new_tokens=2)])
    assert ((spans.ADMIT,), spans.ADMIT_SCATTER, {"rid": 4}) in recorded


def test_tick_spans_and_one_transfer_free_program(model_params, recorded):
    eng = engine(model_params)
    eng.admit([Request(rid=0, prompt=prompt(9), max_new_tokens=12)])
    eng.step()                                   # compiles the tick
    del recorded[:]
    with jax.transfer_guard("disallow"):
        for _ in range(3):
            eng.step()
    assert recorded == [((), spans.STEP, {})] * 3
    del recorded[:]
    eng.sync()
    assert recorded == [((), spans.SYNC, {}),
                        ((spans.SYNC,), spans.SYNC_FETCH, {}),
                        ((spans.SYNC,), spans.SYNC_APPLY, {})]
    assert eng.trace_count == 1
    assert len(eng.slots[0].generated) == 5


def test_router_harvest_spans(model_params, recorded):
    model, params = model_params
    router = make_cells(model, params, ServeConfig(
        batch_slots=2, max_seq_len=32, eos_id=-1, page_size=PS,
        num_pages=8), n_cells=2)
    router.admit([Request(rid=0, prompt=prompt(9), max_new_tokens=6),
                  Request(rid=1, prompt=prompt(9, 50), max_new_tokens=6)])
    del recorded[:]
    router.step()
    router.sync()
    assert recorded == [((), spans.STEP, {}), ((), spans.STEP, {}),
                        ((), spans.SYNC, {}),
                        ((spans.SYNC,), spans.SYNC_FETCH, {}),
                        ((spans.SYNC,), spans.SYNC_APPLY, {}),
                        ((spans.SYNC,), spans.SYNC_APPLY, {})]


def test_tick_hlo_text_is_the_compiled_tick(model_params):
    eng = engine(model_params)
    eng.admit([Request(rid=0, prompt=prompt(9), max_new_tokens=4)])
    eng.step()
    text = eng.tick_hlo_text()
    assert text.startswith("HloModule jit__tick_impl")
    assert 'op_name="jit(_tick_impl)' in text
    assert eng.trace_count == 1                  # served from the trace


@pytest.fixture(scope="module")
def hybrid_params():
    from repro import configs
    model = build_model(configs.get_reduced("granite-4.0-h-micro"),
                        ParallelConfig(remat="none"))
    return model, model.init_params(jax.random.PRNGKey(0))


def test_hybrid_admission_writes_state_per_request(hybrid_params, recorded):
    """A model with recurrent state: its slot's state is written from the
    prefill in a span of its own, after the page write; nothing shared."""
    eng = engine(hybrid_params)
    eng.admit([Request(rid=3, prompt=prompt(16), max_new_tokens=2),
               Request(rid=5, prompt=prompt(16), max_new_tokens=2)])
    A = (spans.ADMIT,)
    assert [(p, n, m) for p, n, m in recorded if p == A] == [
        (A, spans.ADMIT_REAP, {}),
        (A, spans.ADMIT_PREFILL, {"rid": 3}),
        (A, spans.ADMIT_SCATTER, {"rid": 3, "fresh_pages": 3,
                                  "shared_pages": 0}),
        (A, spans.ADMIT_STATE, {"rid": 3}),
        (A, spans.ADMIT_PREFILL, {"rid": 5}),
        (A, spans.ADMIT_SCATTER, {"rid": 5, "fresh_pages": 3,
                                  "shared_pages": 0}),
        (A, spans.ADMIT_STATE, {"rid": 5}),
        (A, spans.ADMIT_FIRST_TOKENS, {}),
    ]


def test_hybrid_tick_scopes_and_live_positions(hybrid_params):
    """The hybrid tick names the Mamba mixers' work ``ssm`` and their
    state's reads and writes ``ssm_state``, beside ``attention`` and
    ``mlp``; its stats count the live slots' positions."""
    eng = engine(hybrid_params)
    eng.admit([Request(rid=0, prompt=prompt(9), max_new_tokens=4),
               Request(rid=1, prompt=prompt(5, 30), max_new_tokens=4)])
    eng.step()
    eng.sync()
    text = eng.tick_hlo_text()
    for scope in ("ssm", "ssm_state", "attention", "kv_read", "mlp",
                  "head"):
        assert f"/{scope}/" in text, scope
    assert eng.tick_stats[0]["live_slots"] == 2
    assert eng.tick_stats[0]["live_positions"] == (9 + 1) + (5 + 1)
